"""chip_smoke.py rehearsed on the CPU, and the helpers it leans on.

The script itself must refuse to run without a TPU; its phase functions,
called here at toy size in this process (Pallas in interpret mode, four
virtual CPU devices for the multi-chip phase), must pass — that is
rehearsals 1 and 2 of the on-chip-measurement guide, kept as tests.
"""
import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: every width and count cut to a toy; the code paths are the real ones
TOY = chip_smoke.Sizes(
    vocab=97, hidden=32, heads=2, layers=2, context=64, slots=2,
    block_len=8, num_blocks=40, prefill_buckets=(8, 32),
    prompt_lens=(5, 20, 5, 20), shared_prefix=16, shared_tail=4, max_new=5,
    flash_cases=((1, 2, 64, 16, "float32"),), flash_block=16,
    listed_cases=(("grouped", 4, 6, 2, 16, 8, 6, 20, "float32"),
                  ("grouped", 4, 18, 2, 16, 16, 5, None, "bfloat16"),
                  ("latent", 4, 4, 1, 36, 16, 5, None, "bfloat16")),
    expert_cases=((48, 40, 8, 64, 128, 5, "float32"),
                  (64, 9, 16, 32, 256, 3, "bfloat16")),
    resnet_depth=20, resnet_dataset="cifar10", image=32, classes=10,
    train_batch=8, train_iters=4, multichip_batch=8, multichip_iters=3)


@pytest.fixture(scope="module")
def probe():
    return chip_smoke.PROBE.install()


# --------------------------------------------------------------------- #
# the script refuses the CPU                                            #
# --------------------------------------------------------------------- #

def test_script_exits_nonzero_on_cpu_and_prints_no_ok(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    for argv in ([], ["--chips", "4"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
            cwd=str(tmp_path), env=env, capture_output=True, text=True,
            timeout=300)
        assert proc.returncode != 0
        assert '"ok": true' not in proc.stdout
        assert "no TPU" in proc.stderr


def test_script_alone_without_the_program_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the import of the program fails, and so does the script."""
    alone = tmp_path / "alone"
    alone.mkdir()
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (alone / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(alone), env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# --------------------------------------------------------------------- #
# the phases at toy size                                                #
# --------------------------------------------------------------------- #

def test_the_only_options_are_chips_and_seed():
    """The LM's depth is the constant 48: no option cuts it (a one-layer
    toy must not be able to print the smoke's last line)."""
    assert chip_smoke.REAL.layers == 48
    with pytest.raises(SystemExit):
        chip_smoke.main(["--lm-layers", "1"])


def test_serve_and_kernels_phases_pass_at_toy_size(probe, capsys):
    import gc
    import weakref
    serve, lm = chip_smoke.phase_serve(TOY, seed=0)
    assert serve["ok"] and serve["streams_exact"] == 6
    # build() made no gradient buffers, and the smoke dropped none
    assert serve["gradient_buffers_allocated"] is False
    assert serve["decode_attn_resolved"] == "gather"
    assert all(v == 0 for v in serve["compiles_after_warmup"].values())
    assert serve["prefix_cache"]["hits"] >= 1
    kernels = chip_smoke.phase_kernels(TOY, seed=0, require_compiled=False)
    assert kernels["ok"]
    # on the CPU the kernels run interpreted — and the probe sees it,
    # which is what makes main() fail such a run on the chip
    assert kernels["pallas_calls_traced"] > 0
    listed = [c for c in kernels["cases"] if "gap_to_float64" in c]
    experts = [c for c in listed if c["kernel"] == "grouped matmul"]
    listed = [c for c in listed if c not in experts]
    assert [c["kernel"] for c in listed] == ["grouped decode"] * 2 + [
        "latent decode"]
    for c in listed:    # exact products on the CPU, in the kernel and the walk
        assert max(c["gap_to_float64"].values()) < 1e-5, c
    assert [c["dtype"] for c in experts] == ["float32", "bfloat16"]
    for c in experts:   # a product's rounding, and no time from a CPU
        assert c["gap_to_float64"]["kernel"] <= max(
            c["gap_to_float64"]["ragged_dot"], 1e-5) and "alone_ms" not in c
    assert {"grouped_decode_attention", "latent_decode_attention",
            "grouped_matmul"} <= set(kernels["pallas_interpreted"])
    assert any(n.startswith("flash_attention")
               for n in kernels["pallas_interpreted"])
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["phase"] for r in rows] == ["serve", "kernels"]
    # what main() does before the kernels: drop the LM.  A closed
    # engine and offline generate() have used the model; no registry and
    # no jit cache of the program may keep it (or its weights) alive —
    # the last reference gone, plain garbage collection takes it
    model = weakref.ref(lm)
    leaf = weakref.ref(jax.tree_util.tree_leaves(lm.params)[0])
    del lm
    gc.collect()
    assert model() is None and leaf() is None
    chip_smoke._free_device_memory()
    # ... and no weight, KV arena or logit row of that LM is left behind
    lm_sized = [a.shape for a in jax.live_arrays()
                if TOY.vocab in a.shape or TOY.num_blocks in a.shape]
    assert not lm_sized, lm_sized


def test_stream_divergence_passes_only_a_near_tie(probe):
    """The replay comparison: equal streams are exact; a stream that
    leaves the oracle at a token the model does not nearly tie fails."""
    model = chip_smoke._build_lm(TOY, seed=0)
    req = chip_smoke._requests(TOY, seed=0)[0]
    want = chip_smoke._offline(model, req, TOY)
    assert chip_smoke._compare_stream(model, req, want.copy(), want,
                                      TOY) == {"exact": True}
    # the greedy token against a token far from the top: a real gap
    logp = model.f(model.params, req["prompt"][None].astype("int32"))[0, -1]
    worst = int(jax.numpy.argmin(logp)) + 1
    bad = want.copy()
    bad[0] = worst
    with pytest.raises(chip_smoke.SmokeFailure, match="diverges"):
        chip_smoke._compare_stream(model, req, bad, want, TOY)
    gap = chip_smoke._near_tie(model, req, [], int(want[0]), int(want[0]),
                               TOY)
    assert gap < 1e-5     # the oracle's own token is the argmax


def test_train_phase_passes_at_toy_size(probe):
    row = chip_smoke.phase_train(TOY, seed=0)
    assert row["ok"] and row["step_programs"] == 1
    assert row["losses"][-1] < row["losses"][0]
    assert row["optimizer"] == "LocalOptimizer"


def test_multichip_phase_passes_on_four_virtual_devices(probe):
    devices = jax.devices()[:4]
    row = chip_smoke.phase_multichip(TOY, seed=0, devices=devices,
                                     require_platform=None)
    assert row["ok"] and len(set(row["mesh_devices"])) == 4
    assert {d for d, _ in row["parameter_shards"]} == set(row["mesh_devices"])
    assert {d for d, _ in row["batch_shards"]} == set(row["mesh_devices"])
    lowered = row["collective_footprint_bytes_lowered"]
    assert lowered["all-gather"] > 0 and lowered["reduce-scatter"] > 0
    # on the CPU the footprint is read off the lowered text, so the pair
    # is there; on the chip the row says what the TPU compiler kept
    assert row["zero1_collectives_in_compiled"] is True
    assert max(row["loss_rel_diff"]) <= chip_smoke.MULTICHIP_LOSS_RTOL
    # and it insists on real chips when asked to
    with pytest.raises(chip_smoke.SmokeFailure, match="not all tpu"):
        chip_smoke.phase_multichip(TOY, seed=0, devices=devices)


def test_preflight_refuses_a_cpu_backend():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke._preflight(1)


# --------------------------------------------------------------------- #
# the compile cache is placed from outside                              #
# --------------------------------------------------------------------- #

def test_compile_cache_honours_the_environment(tmp_path, monkeypatch):
    from bigdl_tpu.utils.engine import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    # set from outside: the program touches nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    from bigdl_tpu.utils.engine import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert configure_compile_cache() == path        # idempotent
        assert "/tmp" not in path and str(os.getpid()) not in path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_private_cache_knob_or_tmp_cache_path_remains():
    """Acceptance grep: no cache path under /tmp, no private knob."""
    import re
    hits = []
    for root in ("chip_smoke.py", "bigdl_tpu"):
        path = os.path.join(REPO, root)
        files = ([path] if os.path.isfile(path) else
                 [os.path.join(d, f) for d, _, fs in os.walk(path)
                  for f in fs if f.endswith(".py")])
        for fn in files:
            with open(fn) as f:
                for i, line in enumerate(f, 1):
                    if re.search(r"BIGDL_TPU_COMPILE_CACHE|/tmp/\S*cache",
                                 line):
                        hits.append(f"{fn}:{i}: {line.strip()}")
    assert not hits, hits


# --------------------------------------------------------------------- #
# peak rates: one table, unknown device an error                        #
# --------------------------------------------------------------------- #

def test_peak_table_holds_the_v5e_row_with_its_source():
    from bigdl_tpu.utils.profiling import DEVICE_PEAKS, device_peaks
    p = device_peaks("TPU v5 lite")
    assert p is DEVICE_PEAKS["TPU v5 lite"]
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_s) == (197e12, 393e12,
                                                         819e9)
    assert "TPU v5e" in p.source


def test_peak_table_raises_on_an_unknown_device_kind():
    from bigdl_tpu.utils.profiling import device_peaks
    with pytest.raises(ValueError, match="no published peak"):
        device_peaks("TPU v9 imaginary")
    # the attached device here is a CPU: no default is assumed for it
    with pytest.raises(ValueError, match="no published peak"):
        device_peaks()


def test_peak_env_overrides_are_gone(monkeypatch):
    """BIGDL_TPU_PEAK_TFLOPS / BIGDL_TPU_HBM_GBPS no longer move the
    peaks, and no module-level assumed constants remain."""
    import importlib
    monkeypatch.setenv("BIGDL_TPU_PEAK_TFLOPS", "1")
    monkeypatch.setenv("BIGDL_TPU_HBM_GBPS", "1")
    from bigdl_tpu.utils import profiling
    profiling = importlib.reload(profiling)
    assert profiling.device_peaks("TPU v5 lite").bf16_flops == 197e12
    assert not hasattr(profiling, "PEAK_FLOPS")
    assert not hasattr(profiling, "PEAK_HBM_BYTES_S")


def test_roofline_attribution_needs_a_known_device():
    """attribute_step_time plans against a named chip; on the CPU with no
    device_kind it raises instead of assuming one."""
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.utils.profiling import attribute_step_time
    model = nn.Sequential(nn.Linear(4, 3)).build(seed=0)
    x = np.zeros((2, 4), np.float32)
    with pytest.raises(ValueError, match="no published peak"):
        attribute_step_time(model, x, 1.0, mode="roofline")
    rows = attribute_step_time(model, x, 1.0, mode="roofline",
                               device_kind="TPU v5 lite")
    assert abs(sum(r["time_s"] for r in rows) - 1.0) < 1e-9


# --------------------------------------------------------------------- #
# the benchmark: no chip, no result                                     #
# --------------------------------------------------------------------- #

def test_benchmark_command_fails_without_a_chip(tmp_path):
    """No fallback may hide the device: the one command the ledger's
    numbers come from refuses a CPU and prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "gpt2xl.steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 accelerator chip" in proc.stderr


def test_ensure_virtual_devices_hands_back_cpu_devices_only():
    from bigdl_tpu.utils.engine import ensure_virtual_devices
    devs = ensure_virtual_devices(4)
    assert len(devs) == 4 and all(d.platform == "cpu" for d in devs)
