"""The serving launcher's flags, its decode check, the compile-cache
placement, and what the chip path imports."""
import os
import subprocess
import sys

import pytest

from repro.launch import compile_cache, serve

jax = pytest.importorskip("jax")


@pytest.fixture
def no_cache_placement(monkeypatch):
    """``main`` places the persistent compile cache; keep test workers'
    jax config untouched."""
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")


def test_smoke_flag_is_an_on_off_pair():
    p = serve.build_parser()
    assert p.parse_args([]).smoke is True
    assert p.parse_args(["--smoke"]).smoke is True
    assert p.parse_args(["--no-smoke"]).smoke is False
    args = p.parse_args([])
    assert (args.backend, args.device_products, args.parity_storage) == (
        "numpy", False, "materialized")


def test_coded_flags_reach_the_bridge(monkeypatch, no_cache_placement):
    import repro.serve_coded as sc
    seen = {}

    class Stop(Exception):
        pass

    def fake_bridge(**kw):
        seen.update(kw)
        raise Stop

    monkeypatch.setattr(sc, "CodedServingBridge", fake_bridge)
    with pytest.raises(Stop):
        serve.main(["--coded", "--backend", "pallas", "--device-products",
                    "--parity-storage", "virtual", "--no-smoke"])
    assert seen["backend"] == "pallas"
    assert seen["device_products"] is True
    assert seen["parity_storage"] == "virtual"
    assert seen["smoke"] is False


def test_plain_launcher_checks_decode_against_full_forward(
        capsys, monkeypatch, no_cache_placement):
    argv = ["--requests", "2", "--prompt-len", "8", "--gen-len", "3"]
    assert serve.main(argv) == 0
    out = capsys.readouterr().out
    assert "decode vs full forward" in out and "ok" in out
    # the check is enforced: a tolerance no float path can meet fails it
    monkeypatch.setitem(serve.DECODE_TOL, "float32", 0.0)
    assert serve.main(argv) == 1
    assert "FAILED" in capsys.readouterr().out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shift", [1, -1])
def test_plain_launcher_catches_decode_position_off_by_one(
        capsys, monkeypatch, no_cache_placement, dtype, shift):
    """A planted decode bug — every step's position (and so its cache slot)
    off by one — must fail the check under the dtype's own tolerance."""
    import dataclasses
    from repro.configs import get_smoke_config
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"), dtype=dtype)
    build, fns = serve.build_model, serve.serving_fns

    def shifted_fns(c, return_hidden=False):
        prefill_fn, decode_fn = fns(c, return_hidden=return_hidden)
        return prefill_fn, (lambda p, t, pos, caches:
                            decode_fn(p, t, pos + shift, caches))

    monkeypatch.setattr(serve, "build_model",
                        lambda arch, smoke=True, seed=0: build(cfg, seed=seed))
    argv = ["--requests", "2", "--prompt-len", "8", "--gen-len", "3"]
    assert serve.main(argv) == 0
    assert "ok" in capsys.readouterr().out
    monkeypatch.setattr(serve, "serving_fns", shifted_fns)
    assert serve.main(argv) == 1
    assert "FAILED" in capsys.readouterr().out


def test_compile_cache_respects_env_and_defaults_in_checkout(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None  # jax reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_path_does_not_import_dryrun():
    """``launch/dryrun.py`` overwrites XLA_FLAGS to fake 512 host devices;
    nothing the chip smoke, the launcher or the benchmarks import may
    pull it in."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys; sys.path[:0] = ['src', '.']\n"
            "import chip_smoke, repro.launch.serve, repro.serve_coded\n"
            "import benchmarks.serve_bench, benchmarks.backend_bench\n"
            "import benchmarks.coded_exec_bench, benchmarks.run\n"
            "assert 'repro.launch.dryrun' not in sys.modules\n"
            "print('clean')")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "clean" in r.stdout, r.stderr[-2000:]
