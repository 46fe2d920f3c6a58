"""End-to-end driver tests: training loop (ckpt/resume) + wave serving."""

import jax

jax.config.update("jax_platform_name", "cpu")


def test_train_driver_loss_decreases(tmp_path):
    from repro.launch import train as train_mod

    losses = train_mod.main([
        "--arch", "smollm-135m", "--smoke", "--steps", "30",
        "--seq-len", "32", "--global-batch", "4",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "10",
        "--log-every", "100", "--peak-lr", "1e-3",
    ])
    assert len(losses) == 30
    assert losses[-1] < losses[0], f"{losses[0]} -> {losses[-1]}"


def test_train_driver_resumes_from_checkpoint(tmp_path):
    from repro import checkpoint as ckpt
    from repro.launch import train as train_mod

    args = ["--arch", "smollm-135m", "--smoke", "--steps", "10",
            "--seq-len", "16", "--global-batch", "2",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
            "--log-every", "100"]
    train_mod.main(args)
    assert ckpt.latest_step(str(tmp_path)) == 10
    # extending the run resumes from step 10 (3 more steps, not 13)
    args[args.index("10")] = "13"
    losses = train_mod.main(args)
    assert len(losses) == 3


def test_serve_driver_all_requests_complete():
    from repro.launch import serve as serve_mod

    stats = serve_mod.main([
        "--arch", "smollm-135m", "--smoke", "--requests", "5",
        "--max-new", "4", "--slots", "2", "--max-len", "32"])
    assert stats["n_requests"] == 5
    assert all(len(v) >= 4 for v in stats["outputs"].values())
    assert stats["tokens_per_s"] > 0


def test_serve_greedy_deterministic():
    from repro.launch import serve as serve_mod

    s1 = serve_mod.main(["--arch", "smollm-135m", "--smoke", "--requests", "2",
                         "--max-new", "4", "--slots", "2", "--max-len", "32"])
    s2 = serve_mod.main(["--arch", "smollm-135m", "--smoke", "--requests", "2",
                         "--max-new", "4", "--slots", "2", "--max-len", "32"])
    assert s1["outputs"] == s2["outputs"]


def test_compilation_cache_dir(monkeypatch, tmp_path):
    """The drivers' cache helper: with ``JAX_COMPILATION_CACHE_DIR`` set
    it adds no other directory; without it the cache goes to the repo's
    fixed ``.jax_cache``."""
    from repro.util import env

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert env.enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert env.enable_compilation_cache() == env.DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == env.DEFAULT_CACHE_DIR
        assert env.DEFAULT_CACHE_DIR.endswith(".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
