"""Writes ``fixture_scopes.xplane.pb``: a hand-checkable trace for the readers
of ``perfbench/layer_metrics/_spans.py``. As on the chip, a device event is
named by its whole HLO instruction and carries no other string; the scope
stack of an instruction is in ``TABLES``, which stands for what the program
hands out (``scope_table``). Times in microseconds; window 0..140.

Device ``/device:TPU:0``, line ``XLA Ops`` (``L`` = ``while/body/closed_call``,
every stack starts ``jit(step)/``):

    fusion.1       0.. 10  loss/jvp(embed)/gather                         embed, fwd
    fusion.2      10.. 30  loss/jvp(layers)/L/attn_proj/dot_general       attn_proj, fwd
                           (the second table has a ``fusion.2`` of another shape under ``head``)
    all-gather.1  30.. 40  loss/jvp(layers)/L/mlp/dot_general             mlp, fwd; alone: exposed 10
    fusion.3      40.. 60  loss/transpose(jvp(layers))/L/checkpoint/rematted_computation/mlp/dot_general
                                                                          mlp, remat
    (idle         60.. 70)
    all-gather.2  70.. 90  loss/transpose(jvp(layers))/L/checkpoint/rematted_computation/attn_proj/dot_general
                                                                          attn_proj, remat; exposed 70..80
    fusion.4      80..100  loss/transpose(jvp(layers))/L/checkpoint/attn_kernel/pallas_call[name=flash]
                                                                          attn_kernel, bwd
    fusion.5     100..110  loss/transpose(jvp(layers))/while/body/dynamic_update_slice
                                                                          layer_carry, bwd
    (idle        110..120)
    fusion.6     120..135  optimizer/mul                                  optimizer
    copy.1       135..140  (in no table: the compiler's own)              unscoped, other

Busy 60 + 40 + 20 = 120; idle 20 (14.2857 % of 140). Self times: all-gather.2
has 10 of its 20 (fusion.4 covers 80..90), every other event its duration.

    scope, % of 120: embed 10 -> 8.3333; attn_proj 20 + 10 -> 25; mlp 10 + 20 -> 25;
        attn_kernel 20 -> 16.6667; layer_carry 10 -> 8.3333; optimizer 15 -> 12.5;
        unscoped 5 -> 4.1667 (``copy``)
    pass, % of 120: fwd 10 + 20 + 10 -> 33.3333; remat 20 + 10 -> 25; bwd 20 + 10 -> 25;
        optimizer 12.5; other 4.1667
    exposed collective by pass, % of 140: fwd 10 -> 7.142857; remat 10 -> 7.142857
        (together 14.2857 = coll.exposed_pct)

Host thread: ``perfbench/engine.step`` 5..65 and 66..125. The test lays two
flight iterations over them, stamped on the wall clock at session start
``S`` plus 5 and plus 66, each 58 long:

    A (5..63):   schedule 5..9, prefill 9..25, dispatch 25..55, device_wait 55..61, harvest 61..63
    B (66..124): schedule 66..76, prefill 76..96, dispatch 96..111, device_wait 111..122, harvest 122..124

    gap 60..70:   device_wait 1, harvest 2, outside_step 3 (63..66), schedule 4
    gap 110..120: dispatch 1, device_wait 9
    idle by phase, % of 140: schedule 4 -> 2.857143; prefill 0; dispatch 1 -> 0.714286;
        device_wait 10 -> 7.142857; harvest 2 -> 1.428571; outside_step 3 -> 2.142857
        (together 14.2857 = device.idle_pct)

Run ``python perfbench/tests/make_fixture_scopes.py`` to write it again.
"""

import os

US = 1_000_000  # picoseconds
L = "while/body/closed_call"
BWD = f"jit(step)/loss/transpose(jvp(layers))/{L}/checkpoint"

OPS = [  # (instruction, result shape, opcode, start, duration, scope stack)
    ("fusion.1", "bf16[8,64]", "fusion", 0, 10, "jit(step)/loss/jvp(embed)/gather"),
    ("fusion.2", "bf16[8,32]", "fusion", 10, 20,
     f"jit(step)/loss/jvp(layers)/{L}/attn_proj/dot_general"),
    ("all-gather.1", "bf16[64,32]", "all-gather", 30, 10,
     f"jit(step)/loss/jvp(layers)/{L}/mlp/dot_general"),
    ("fusion.3", "bf16[8,96]", "fusion", 40, 20, f"{BWD}/rematted_computation/mlp/dot_general"),
    ("all-gather.2", "bf16[64,32]", "all-gather", 70, 20,
     f"{BWD}/rematted_computation/attn_proj/dot_general"),
    ("fusion.4", "bf16[8,4,8]", "fusion", 80, 20, f"{BWD}/attn_kernel/pallas_call[name=flash]"),
    ("fusion.5", "bf16[2,8,64]", "fusion", 100, 10,
     "jit(step)/loss/transpose(jvp(layers))/while/body/dynamic_update_slice"),
    ("fusion.6", "f32[64,32]", "fusion", 120, 15, "jit(step)/optimizer/mul"),
    ("copy.1", "f32[64,32]", "copy", 135, 5, ""),
]
HOST = [("perfbench/engine.step", 5, 60), ("perfbench/engine.step", 66, 59)]

#: what the program's ``scope_table`` would return, for two programs: the
#: second shares the name ``fusion.2`` with the first, at another shape
TABLES = [
    {name: (shape, stack) for name, shape, _, _, _, stack in OPS if stack},
    {"fusion.2": ("f32[8,256]", "jit(other)/head/dot_general")},
]


def _line(name, shape, opcode):
    """An event's name as the TPU's trace writes it: the HLO instruction."""
    return f"%{name} = {shape}{{1,0:T(8,128)}} {opcode}({shape}{{1,0}} %operand.7), kind=kLoop"


def text_proto() -> str:
    ops = [(_line(n, sh, op), start, dur) for n, sh, op, start, dur, _ in OPS]
    names = sorted({n for n, *_ in ops} | {n for n, *_ in HOST})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in ids.items())

    def events(rows):
        return " ".join(f"events {{ metadata_id: {ids[name]} offset_ps: {start * US} "
                        f"duration_ps: {dur * US} }}" for name, start, dur in rows)

    device = (f'planes {{ id: 1 name: "/device:TPU:0" '
              f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events(ops)} }} {meta} }}')
    host = (f'planes {{ id: 2 name: "/host:CPU" '
            f'lines {{ id: 2 name: "perfbench-engine-loop" timestamp_ns: 0 {events(HOST)} }} '
            f'{meta} }}')
    return device + " " + host


def write(path: str) -> str:
    from jax.profiler import ProfileData

    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text_proto()))
    return path


if __name__ == "__main__":
    print(write(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "fixture_scopes.xplane.pb")))
