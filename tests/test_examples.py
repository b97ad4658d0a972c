"""Run every shipped example on the virtual CPU mesh (reference
``tests/test_examples.py`` runs its examples with mocked dataloaders; here
the vendored dataset makes them fully runnable) + quality bars (the
reference's ``external_deps/test_performance.py`` pins accuracy per
config).

Examples execute in-process (``runpy``) so they share the XLA compile
cache — the scripts use identical model/batch shapes, so the whole file
compiles once — but for the two whose step has hung under a loaded machine,
which run in a child with a time limit (:func:`_run_in_child`). The launcher
boundary is still covered by one subprocess test. The conftest fixture
resets the state singletons between tests.
"""

import contextlib
import io
import os
import re
import runpy
import subprocess
import sys

import pytest

pytestmark = pytest.mark.examples  # end-to-end example runs: slowest lane (make test_all)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")
BY_FEATURE = os.path.join(EXAMPLES, "by_feature")


def _run(script, *args):
    """Execute an example in-process with argv patched; returns stdout."""
    path = script if os.path.isabs(script) else os.path.join(EXAMPLES, script)
    old_argv, old_cwd = sys.argv, os.getcwd()
    added = EXAMPLES not in sys.path
    if added:
        sys.path.insert(0, EXAMPLES)
    sys.argv = [path, *args]
    os.chdir(EXAMPLES)
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            runpy.run_path(path, run_name="__main__")
    finally:
        sys.argv = old_argv
        os.chdir(old_cwd)
        if added:
            sys.path.remove(EXAMPLES)
    return buf.getvalue()


def _run_in_child(script, *args, timeout=300):
    """Execute an example in a process of its own, under this one's
    eight-device flags (``conftest.py``'s ``XLA_FLAGS`` and ``NPROC``), and
    return its stdout. For the examples whose eight-device step has hung
    under the six workers of the whole run (ROADMAP Design, "The unsteady
    examples"): in-process, a rendezvous that never completes holds the xdist
    worker for the 600 s of ``--xla_cpu_collective_call_terminate_timeout_seconds``
    and then aborts it, queue and all; here it fails this one test at
    ``timeout`` and the worker lives. What the example has to print is
    asserted as before."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        filter(None, [REPO, EXAMPLES, os.environ.get("PYTHONPATH")])))
    out = subprocess.run(
        [sys.executable, script, *args], capture_output=True, text=True, cwd=EXAMPLES,
        timeout=timeout, env=env)
    assert out.returncode == 0, f"{script} failed:\n{out.stdout[-2000:]}\n{out.stderr[-4000:]}"
    return out.stdout


def test_nlp_example_reaches_quality_bar():
    stdout = _run("nlp_example.py", "--num_epochs", "2")
    last = [l for l in stdout.splitlines() if l.startswith("epoch")][-1]
    acc = float(last.split("'accuracy': ")[1].split(",")[0].rstrip("}"))
    assert acc >= 0.85, f"accuracy bar missed: {last}"


def test_complete_nlp_example_checkpoints_and_tracks(tmp_path):
    stdout = _run(
        "complete_nlp_example.py", "--num_epochs", "1",
        "--checkpointing_steps", "epoch", "--with_tracking",
        "--output_dir", str(tmp_path),
    )
    assert "epoch 0" in stdout
    assert (tmp_path / "epoch_0").is_dir()
    assert any(p.name.startswith("complete_nlp") for p in tmp_path.iterdir())


def test_complete_nlp_example_resumes(tmp_path):
    _run(
        "complete_nlp_example.py", "--num_epochs", "1",
        "--checkpointing_steps", "epoch", "--output_dir", str(tmp_path),
    )
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state(reset_partial_state=True)
    GradientState._reset_state()
    stdout = _run(
        "complete_nlp_example.py", "--num_epochs", "2",
        "--resume_from_checkpoint", str(tmp_path / "epoch_0"),
        "--output_dir", str(tmp_path),
    )
    assert "Resumed from checkpoint" in stdout
    assert "epoch 1" in stdout and "epoch 0:" not in stdout  # skipped epoch 0


def test_gradient_accumulation_example():
    stdout = _run_in_child(
        os.path.join(BY_FEATURE, "gradient_accumulation.py"), "--num_epochs", "1"
    )
    assert "epoch 0" in stdout


def test_checkpointing_example(tmp_path):
    stdout = _run(
        os.path.join(BY_FEATURE, "checkpointing.py"), "--num_epochs", "1",
        "--output_dir", str(tmp_path),
    )
    assert "epoch 0" in stdout
    assert (tmp_path / "checkpoints" / "checkpoint_0").is_dir()


def test_memory_example():
    stdout = _run(os.path.join(BY_FEATURE, "memory.py"), "--num_epochs", "1")
    assert "ran with batch sizes: [16]" in stdout


def test_profiler_example(tmp_path):
    _run(
        os.path.join(BY_FEATURE, "profiler.py"), "--trace_dir", str(tmp_path),
        "--profile_steps", "2",
    )
    found = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert found, "no trace files written"


def test_early_stopping_example():
    stdout = _run(os.path.join(BY_FEATURE, "early_stopping.py"), "--num_epochs", "4")
    assert "early stop at" in stdout


def test_local_sgd_example():
    stdout = _run(os.path.join(BY_FEATURE, "local_sgd.py"), "--num_epochs", "1")
    assert "final loss" in stdout


def test_ddp_comm_hook_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "ddp_comm_hook.py"), "--num_epochs", "2",
        "--comm_hook", "bf16",
    )
    assert "grad comm hook: bf16" in stdout  # active on the 8-device dp mesh
    last = [l for l in stdout.splitlines() if l.startswith("epoch")][-1]
    acc = float(last.split("'accuracy': ")[1].split(",")[0].rstrip("}"))
    # same bar as the canonical nlp example at 2 epochs: the compressed
    # reduction must not cost convergence
    assert acc >= 0.85, f"comm-hook training underperformed: {last}"


def test_context_parallel_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "context_parallel.py"),
        "--cp", "4", "--mode", "ring", "--seq", "128", "--steps", "24",
    )
    assert "'cp': 4" in stdout
    m = re.search(r"recall loss ([\d.]+) -> ([\d.]+)", stdout)
    assert m, stdout
    assert float(m.group(2)) < float(m.group(1))  # recall task is learnable


def test_megatron_lm_pretraining_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "megatron_lm_pretraining.py"),
        "--tp", "2", "--pp", "2", "--num_micro_batches", "4", "--num_epochs", "1",
    )
    assert "'pp': 2" in stdout and "'tp': 2" in stdout
    m = re.search(r"pretraining loss ([\d.]+) -> ([\d.]+)", stdout)
    assert m, stdout
    assert float(m.group(2)) < float(m.group(1))  # bigram structure is learnable


def test_tracking_example(tmp_path):
    stdout = _run(
        os.path.join(BY_FEATURE, "tracking.py"), "--num_epochs", "1",
        "--project_dir", str(tmp_path),
    )
    assert "epoch 0" in stdout
    assert any(tmp_path.iterdir()), "tracker wrote nothing"


def test_multi_process_metrics_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "multi_process_metrics.py"), "--num_epochs", "1"
    )
    assert "exact over 160 samples" in stdout


def test_fsdp_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "fsdp_with_peak_mem_tracking.py"),
        "--steps", "4", "--fsdp_degree", "2",
    )
    assert "loss" in stdout and "peak_mem" in stdout


@pytest.mark.slow
def test_nlp_example_under_launcher():
    """The example must also run through the product's own launcher
    (reference pattern: ``tests/test_examples.py`` + ``accelerate launch``)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.accelerate_cli", "launch",
            "--num_cpu_devices", "8",
            os.path.join(EXAMPLES, "nlp_example.py"), "--num_epochs", "1",
        ],
        capture_output=True, text=True, cwd=EXAMPLES, timeout=420, env=env,
    )
    assert out.returncode == 0, f"launch failed:\n{out.stdout}\n{out.stderr}"
    assert "epoch 0" in out.stdout


def test_cv_example_reaches_quality_bar():
    stdout = _run("cv_example.py", "--num_epochs", "8")
    last = [l for l in stdout.splitlines() if l.startswith("epoch")][-1]
    acc = float(last.split("accuracy ")[1])
    assert acc >= 0.8, f"cv accuracy bar missed: {last}"


def test_deepspeed_config_example():
    stdout = _run_in_child(
        os.path.join(BY_FEATURE, "deepspeed_with_config_support.py"), "--num_epochs", "1"
    )
    assert "resolved ds config" in stdout and '"auto"' not in stdout.split("resolved ds config:")[1].splitlines()[0]


def test_cross_validation_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "cross_validation.py"), "--num_folds", "2",
        "--num_epochs", "1",
    )
    assert "cross-validated accuracy" in stdout


def test_pippy_inference_examples():
    stdout = _run(
        os.path.join(EXAMPLES, "inference", "pippy", "llama.py"),
        "--layers", "4", "--hidden", "64", "--batch", "4", "--seq", "16",
    )
    assert "stages split at" in stdout and "logits" in stdout
    stdout = _run(
        os.path.join(EXAMPLES, "inference", "pippy", "gpt2.py"),
        "--layers", "4", "--batch", "4", "--seq", "16",
    )
    assert "stages split at" in stdout
    stdout = _run(
        os.path.join(EXAMPLES, "inference", "pippy", "t5.py"),
        "--layers", "2", "--batch", "4", "--seq", "16", "--dec_seq", "8",
    )
    assert "stages split at" in stdout
    stdout = _run(
        os.path.join(EXAMPLES, "inference", "pippy", "bert.py"),
        "--layers", "4", "--batch", "4", "--seq", "16",
    )
    assert "stages split at" in stdout


def test_split_inference_example():
    stdout = _run(
        os.path.join(EXAMPLES, "inference", "distributed", "split_inference.py"),
        "--num_prompts", "4",
    )
    assert "next-token predictions" in stdout


def test_distributed_inference_task_examples():
    """The task-shaped distributed-inference quartet (reference ships six
    Hub-checkpoint scripts; these run the same distribution patterns with
    synthetic weights)."""
    d = os.path.join(EXAMPLES, "inference", "distributed")
    assert "generated 4 images" in _run(
        os.path.join(d, "distributed_image_generation.py"), "--prompts", "4", "--steps", "4"
    )
    assert "synthesised" in _run(
        os.path.join(d, "distributed_speech_generation.py"),
        "--chunks", "3", "--codes_per_chunk", "4",
    )
    assert "answered" in _run(os.path.join(d, "florence2.py"), "--images", "2")
    assert "denoised" in _run(os.path.join(d, "stable_diffusion.py"), "--steps", "4")


def test_phi2_low_memory_example():
    stdout = _run(
        os.path.join(EXAMPLES, "inference", "distributed", "phi2.py"),
        "--prompts", "3", "--new_tokens", "4",
    )
    assert "generated 4 tokens for 3 prompts" in stdout


def test_config_yaml_templates_load():
    from accelerate_tpu.commands.config import ClusterConfig

    tpl_dir = os.path.join(EXAMPLES, "config_yaml_templates")
    for name in os.listdir(tpl_dir):
        cfg = ClusterConfig.load(os.path.join(tpl_dir, name))
        env = cfg.to_environment()
        assert "ACCELERATE_MIXED_PRECISION" in env, name


def test_deepspeed_templates_ingest():
    from accelerate_tpu import DeepSpeedPlugin

    tpl_dir = os.path.join(EXAMPLES, "deepspeed_config_templates")
    p2 = DeepSpeedPlugin(hf_ds_config=os.path.join(tpl_dir, "zero_stage2_config.json"))
    assert p2.zero_stage == 2
    p3 = DeepSpeedPlugin(hf_ds_config=os.path.join(tpl_dir, "zero_stage3_offload_config.json"))
    assert p3.zero_stage == 3 and p3.offload_param_device == "cpu"


def test_schedule_free_example():
    stdout = _run(os.path.join(BY_FEATURE, "schedule_free.py"), "--num_epochs", "1")
    assert "epoch 0" in stdout


def test_automatic_gradient_accumulation_example():
    stdout = _run(
        os.path.join(BY_FEATURE, "automatic_gradient_accumulation.py"), "--num_epochs", "1"
    )
    assert "ran with (batch_size, accumulation): [(16, 1)]" in stdout
