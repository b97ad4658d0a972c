"""Writes ``fixture.xplane.pb``: a hand-checkable trace of one device and
one host thread (times in microseconds below; the file holds picoseconds).

Device ``/device:TPU:0``, line ``XLA Ops``:
    while.1            0 ..  60   (holds the three below)
      fusion.1         0 ..  20
      paged_attention 20 ..  50   (a custom call; the kernel's name is in a stat)
      all-gather.1    50 ..  60   (nothing else runs: exposed 10)
    all-reduce.2      70 ..  90   (fusion.2 runs 80..100: exposed 10)
    fusion.2          80 .. 100
  busy = union = 60 + 30 = 90 of a 100 window; one idle gap 60..70.
Line ``XLA Modules``: jit_decode(7) 0..60, jit_prefill_plain(9) 70..100.
Host thread: perfbench/engine.step 55..75 (covers the whole gap).

Run ``python perfbench/tests/make_fixture.py`` to write it again.
"""

import os

US = 1_000_000  # picoseconds

EVENTS = {  # metadata id -> name
    1: "while.1", 2: "fusion.1", 3: "custom-call.5", 4: "all-gather.1",
    5: "all-reduce.2", 6: "fusion.2", 7: "jit_decode(7)", 8: "jit_prefill_plain(9)",
    9: "perfbench/engine.step",
}
OPS = [(1, 0, 60), (2, 0, 20), (3, 20, 30), (4, 50, 10), (5, 70, 20), (6, 80, 20)]
MODULES = [(7, 0, 60), (8, 70, 30)]
HOST = [(9, 55, 20)]


def _events(rows, with_stat=False):
    out = []
    for mid, start, dur in rows:
        stat = ""
        if with_stat and mid == 3:
            stat = ' stats { metadata_id: 1 str_value: "jit(decode)/pallas_call[name=paged_attention]" }'
        out.append(f"events {{ metadata_id: {mid} offset_ps: {start * US} duration_ps: {dur * US}{stat} }}")
    return " ".join(out)


def text_proto() -> str:
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for i, n in EVENTS.items())
    stat_meta = 'stat_metadata { key: 1 value { id: 1 name: "tf_op" } }'
    device = (
        f'planes {{ id: 1 name: "/device:TPU:0" '
        f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_events(OPS, True)} }} '
        f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {_events(MODULES)} }} '
        f'{meta} {stat_meta} }}')
    host = (
        f'planes {{ id: 2 name: "/host:CPU" '
        f'lines {{ id: 3 name: "perfbench-engine-loop" timestamp_ns: 0 {_events(HOST)} }} '
        f'{meta} }}')
    return device + " " + host


def write(path: str) -> str:
    from jax.profiler import ProfileData

    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text_proto()))
    return path


if __name__ == "__main__":
    print(write(os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture.xplane.pb")))
