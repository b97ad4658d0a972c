"""The hybrid cell's check against its own seed: the reference with its state
held narrow (the one thing it changes), ``narrow_state_share`` on made-up
readings, and on a tiny engine served in bfloat16 - sound under the limit on
every seed tried, the state stored in bfloat16 over it."""

import jax
import numpy as np
import pytest

from perfbench import check, common
from perfbench.reference import granite_hybrid as reference

CELL = "granite4h-micro-chat-short"
SEED = 2_700_000_021


def _tiny(**changes) -> dict:
    config = common.read_json("perfbench/configs/granite-4.0-h-micro-serve-v5e1.json")
    return {**config, **config["rehearsal"], **changes}


def _ids(n=128, valid=100):
    ids = np.random.default_rng(0).integers(0, 256, size=n).astype(np.int32)
    return ids, valid, np.arange(valid - 40, valid)


#: the exact forward on (the rehearsal's sizes, SEED, ``_ids()``), read from
#: the reference as it stood before it took ``state_dtype`` (git 067f088):
#: logits [0, :3], [20, 100:103] and [39, -3:]
BEFORE = np.array([[0.5161438, 0.3488799, -0.053013954],
                   [0.25577956, -0.24805808, 0.6677176],
                   [-0.20099467, 0.028533107, 0.11618873]], np.float32)


def test_a_float32_state_is_the_forward_as_it_was():
    cfg = _tiny()
    ids, valid, rows = _ids()
    exact = np.asarray(reference.logits_at(cfg, SEED, ids, valid, rows, "bfloat16"))
    got = np.stack([exact[0, :3], exact[20, 100:103], exact[39, -3:]])
    np.testing.assert_allclose(got, BEFORE, rtol=0, atol=2e-6)
    named = np.asarray(reference.logits_at(cfg, SEED, ids, valid, rows, "bfloat16",
                                           state_dtype="float32"))
    assert np.array_equal(exact, named)


def _mixer_text(state_dtype: str) -> str:
    cfg = _tiny()
    z = reference._sizes(cfg)
    shapes = {k.split(".")[-1]: v[1:] for k, v in reference.leaf_shapes(cfg).items()
              if k.startswith("layers.mamba.")}
    w = {n: jax.ShapeDtypeStruct(shapes[n], np.float32) for n in reference.MAMBA_LEAVES}
    y = jax.ShapeDtypeStruct((16, z["h"]), np.float32)
    return str(jax.make_jaxpr(lambda w, y: reference.mamba_mixer(cfg, w, y, state_dtype))(w, y))


def test_a_narrow_state_rounds_what_is_carried_and_nothing_else():
    """The two forwards are the same operations but one: the carried state
    rounded once a token, inside the scan."""
    wide, narrow = _mixer_text("float32"), _mixer_text("bfloat16")
    assert "reduce_precision" not in wide
    assert narrow.count("reduce_precision") == 1
    drop = [line for line in narrow.splitlines() if "reduce_precision" not in line]
    assert len(drop) == len(wide.splitlines())
    cfg = _tiny()
    ids, valid, rows = _ids()
    a = np.asarray(reference.logits_at(cfg, SEED, ids, valid, rows, "bfloat16"))
    b = np.asarray(reference.logits_at(cfg, SEED, ids, valid, rows, "bfloat16",
                                       state_dtype="bfloat16"))
    assert 0 < np.abs(a - b).mean() < 0.05 * a.std()


# -- the number on made-up readings ---------------------------------------------------

def _made_up(monkeypatch, reported_from: str, limits: dict, narrow="bfloat16"):
    """``check.served`` over two requests whose reference readings are made
    up: ``exact`` and a narrow-state forward that moves them by ``d``."""
    rng = np.random.default_rng(7)
    n = (60, 90)
    exact = [rng.normal(-2.0, 1.0, size=k) for k in n]
    d = [rng.normal(0.0, 0.1, size=k) for k in n]
    noise = [rng.normal(0.0, 0.04, size=k) for k in n]
    sample = []
    for e, dd, nn in zip(exact, d, noise):
        reported = e + nn + (dd if reported_from == "narrow" else 0.0)
        sample.append((np.zeros(5, np.int32), np.zeros(len(e), np.int32), list(reported)))
    seen = iter([x for e, dd in zip(exact, d) for x in (e, e + dd)] if narrow
                else list(exact))
    asked = []

    def readings(config, seed, prompt, tokens, served_dtype, **forward):
        asked.append(forward)
        lp = next(seen)
        return {"gaps": np.zeros(len(lp)), "logprobs": lp, "logit_std": 1.0}

    monkeypatch.setattr(check, "sequence_readings", readings)
    cfg = {"check": {"limits": limits, **({"narrow_state": narrow} if narrow else {})}}
    return check.served(cfg, 1, sample, "bfloat16"), asked


def test_the_share_is_nought_for_noise_and_one_for_a_narrow_state(monkeypatch):
    limits = {"gap_max": 0.3, "narrow_state_share": 0.2}
    sound, asked = _made_up(monkeypatch, "exact", limits)
    assert asked == [{}, {"state_dtype": "bfloat16"}] * 2
    assert sound["ok"] and abs(sound["numbers"]["narrow_state_share"]) < 0.1
    assert sound["numbers"]["logprob_err_mean"] == pytest.approx(0.032, rel=0.2)
    narrow, _ = _made_up(monkeypatch, "narrow", limits)
    assert not narrow["ok"] and narrow["numbers"]["narrow_state_share"] == pytest.approx(1.0, abs=0.1)


def test_a_file_that_names_the_share_and_no_narrow_state_is_not_correct(monkeypatch):
    out, asked = _made_up(monkeypatch, "exact", {"gap_max": 0.3, "narrow_state_share": 0.2},
                          narrow=None)
    assert asked == [{}, {}]  # no second pass was paid for
    assert not out["ok"] and "narrow_state_share" in out["reason"]
    plain, _ = _made_up(monkeypatch, "exact", {"gap_max": 0.3, "logprob_err_mean": 0.05},
                        narrow=None)
    assert plain["ok"] and "narrow_state_share" not in plain["numbers"]


# -- the number on a tiny engine served in bfloat16 -----------------------------------

#: prompts and answers long enough for a carried state to matter (the
#: rehearsal's are a dozen tokens)
TRAFFIC = {"prompt_tokens": {"dist": "uniform", "min": 60, "max": 120},
           "output_tokens": {"dist": "uniform", "min": 60, "max": 100}, "rate_rps": 4.0}
#: the committed limit is for 12 requests of hundreds of tokens; 8 requests of
#: 60-100 tokens at width 64 read a sound engine at -0.22..0.10 and the state
#: in bfloat16 at 0.63..0.98 (seeds 21-28, this CPU), so the tiny limit is wider
TINY_LIMIT = 0.4


def _served_in_bfloat16(seed: int, narrow: bool) -> dict:
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    committed = config["check"]
    config, traffic = common.apply_rehearsal(config, traffic)
    flags = list(config["serve_flags"])
    flags[flags.index("--dtype") + 1] = "bf16"
    config = {**config, "serve_flags": flags,
              "check": {**config["check"], "sample_requests": 8,
                        "narrow_state": committed["narrow_state"],
                        "limits": {"gap_max": 1.0, "narrow_state_share": TINY_LIMIT}}}
    control = committed["control"]["serve_flags_replace"]
    ctx = common.Ctx(cell=cell, config=config, traffic={**traffic, **TRAFFIC}, seed=seed,
                     seconds=2.0, trace=False, rehearse=True,
                     serve_flags=flags + [x for kv in control.items() for x in kv] if narrow else None)
    return common.load_driver("serve_engine").run(ctx)


@pytest.mark.parametrize("seed", [21, 22, 23, 24, 25, 26, 27, 28])
def test_the_share_holds_a_sound_engine_and_not_a_state_in_bfloat16(seed):
    sound = _served_in_bfloat16(seed, narrow=False)
    narrow = _served_in_bfloat16(seed, narrow=True)
    assert sound["failed"] == 0 and narrow["failed"] == 0
    s, n = sound["check"]["numbers"], narrow["check"]["numbers"]
    assert sound["correct"] and s["narrow_state_share"] < 0.25, s
    assert not narrow["correct"] and n["narrow_state_share"] > 0.55, n
    # the mean error, which the committed file no longer holds, tells them apart
    # by less than sound seeds differ
    assert n["logprob_err_mean"] < 3 * s["logprob_err_mean"]


def test_the_rehearsal_in_float32_pays_for_no_second_pass():
    bench = common.benchmark()
    cell, config, traffic = common.find_cell(bench, CELL)
    config, traffic = common.apply_rehearsal(config, traffic)
    assert "narrow_state" not in config["check"]
    assert set(config["check"]["limits"]) == {"gap_max", "logprob_err_mean"}
