"""End-to-end driver tests: train.py (with resume) and serve.py as CLIs."""
import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
from subproc import run_child, run_cli


@pytest.mark.parametrize("env_dir", [None, "elsewhere/jax_cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """A set JAX_COMPILATION_CACHE_DIR is JAX's own to read: nothing is set
    in code.  Unset, the cache goes to the checkout's fixed .jax_cache."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = enable_compile_cache()
    if env_dir:
        assert got == env_dir and updates == []
    else:
        assert DEFAULT_DIR.name == ".jax_cache"
        assert (DEFAULT_DIR.parent / "chip_smoke.py").exists()
        assert updates == [("jax_compilation_cache_dir", str(DEFAULT_DIR))]
        assert got == str(DEFAULT_DIR)


@pytest.mark.slow
def test_train_driver_then_resume(tmp_path):
    common = ["repro.launch.train", "--arch", "tinyllama-1.1b", "--smoke",
              "--batch", "4", "--seq", "64", "--save-every", "4",
              "--ckpt-dir", str(tmp_path), "--log-every", "4"]
    out1 = run_cli(common + ["--steps", "6"])
    assert "[done] step 6" in out1
    out2 = run_cli(common + ["--steps", "10"])
    assert "[resume] from step 6" in out2
    assert "[done] step 10" in out2


@pytest.mark.slow
def test_serve_driver(tmp_path):
    out = run_cli(["repro.launch.serve", "--arch", "tinyllama-1.1b",
                   "--smoke", "--requests", "4", "--batch-slots", "2",
                   "--gen", "4", "--prompt-len", "8", "--max-len", "16"])
    # regression: finished requests used to be freed from their slot in the
    # same pass that marked them done, so the driver's `done` list stayed
    # empty; the driver now exits non-zero unless every request completes
    assert "[serve/dense] 4 requests completed" in out


@pytest.mark.slow
def test_serve_driver_paged_preemption(tmp_path):
    """Paged CLI with a pool too small for all slots: preemption +
    requeue must still complete every request."""
    out = run_cli(["repro.launch.serve", "--arch", "tinyllama-1.1b",
                   "--smoke", "--requests", "6", "--batch-slots", "3",
                   "--gen", "24", "--prompt-len", "16", "--max-len", "64",
                   "--cache", "paged", "--page-size", "16", "--pages", "7"])
    assert "[serve/paged] 6 requests completed" in out


@pytest.mark.slow
def test_serve_driver_traffic_replay(tmp_path):
    """Open-loop traffic mode: every arrival completes with TTFT/TPOT
    accounting on the paged cache."""
    out = run_cli(["repro.launch.serve", "--arch", "tinyllama-1.1b",
                   "--smoke", "--traffic", "--cache", "paged",
                   "--requests", "10", "--batch-slots", "4", "--rate", "8",
                   "--gen", "8", "--prompt-len", "16", "--max-len", "64",
                   "--page-size", "16"])
    assert "traffic: 10 requests" in out
    assert "ttft p50/p99" in out


@pytest.mark.slow
def test_train_driver_self_healing_cli(tmp_path):
    """The --hosts CLI path: injected straggler → evict → rebalance."""
    out = run_cli(["repro.launch.train", "--arch", "tinyllama-1.1b",
                   "--smoke", "--steps", "12", "--batch", "8",
                   "--seq", "64", "--hosts", "2", "--inject-slow", "1:4:5",
                   "--straggler-warmup", "2", "--patience", "2",
                   "--save-every", "4", "--log-every", "4",
                   "--ckpt-dir", str(tmp_path),
                   "--overrides", "n_layers=2"], devices=4)
    assert "[evict] hosts [1]" in out
    assert "[rebalance] resumed" in out
    assert "phase DONE, 1 eviction(s)" in out


@pytest.mark.slow
def test_train_driver_multimodal_vlm(tmp_path):
    """--model alias + the vlm path: MultimodalPipeline feeds patch_embeds
    through the standard (non-pipelined) engine."""
    out = run_cli(["repro.launch.train", "--model", "qwen2-vl-2b",
                   "--smoke", "--steps", "3", "--batch", "2", "--seq", "64",
                   "--log-every", "1", "--ckpt-dir", str(tmp_path)])
    assert "[done] step 3" in out


def test_train_driver_vlm_rejects_pp(tmp_path):
    """The executable pipeline engine cannot stage the vision frontend:
    --pp on a vlm arch must fail loudly, and --auto must never route
    there (regression: auto used to pick pp=2 and crash in M-RoPE)."""
    p = run_child(
        ["-m", "repro.launch.train", "--model", "qwen2-vl-2b", "--smoke",
         "--pp", "2", "--steps", "1", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path)], devices=2, timeout=300)
    assert p.returncode != 0
    assert "does not apply to vlm" in p.stderr


@pytest.mark.slow
def test_train_driver_auto_vlm_stays_unpipelined(tmp_path):
    out = run_cli(["repro.launch.train", "--model", "qwen2-vl-2b",
                   "--smoke", "--auto", "--steps", "2", "--batch", "4",
                   "--seq", "32", "--ckpt-dir", str(tmp_path)])
    assert "[auto] chose:" in out
    assert "pipeline" not in out.split("[auto] chose:")[1].splitlines()[0]
    assert "[done] step 2" in out
