"""chip_smoke.py's phases at smoke size on the CPU, with interpret-mode
kernels, so the chip script cannot rot between chip runs.  Only the
script's ``main()`` insists on a TPU."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMALL = dict(n_blocks=16, n_groups=3, rows=1000)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_path_phase_device_matches_host(smoke, capsys):
    out = smoke.phase_main_path(**SMALL, expect_platform="cpu")
    assert out["max_rel_values"] <= smoke.REL_TOL
    assert out["max_rel_var"] <= smoke.VAR_REL_TOL
    assert out["max_rel_state"] <= smoke.REL_TOL
    assert 0 < out["inside"] <= out["earned"]
    printed = capsys.readouterr().out
    assert "best-effort" in printed and "earned bounds" in printed


def test_kernel_phase_in_interpret_mode(smoke):
    worst = smoke.phase_kernels(tm=64, interpret=True)
    assert {"isla_moments_pallas", "isla_moments_batched_pallas",
            "isla_moments_grouped_pallas", "isla_fused_pallas",
            "pilot_stats_pallas"} <= set(worst)
    assert max(worst.values()) <= smoke.REL_TOL


def test_mesh_phase_over_visible_devices(smoke):
    out = smoke.phase_mesh(**SMALL)
    assert out["max_rel_values"] <= smoke.REL_TOL
    assert out["max_rel_state"] <= smoke.REL_TOL


def test_mix_coverage_check_rejects_a_thin_mix(smoke):
    batches = smoke.query_mix(16, 4, 6, smoke.SEED, smoke.PRECISION)
    smoke.check_mix_coverage(batches)
    with pytest.raises(smoke.SmokeFailure, match="lacks"):
        smoke.check_mix_coverage([[q for q in batches[0]
                                   if q.agg != "VAR"][:1]])


def test_main_refuses_a_non_tpu_backend(smoke, capsys, monkeypatch,
                                        tmp_path):
    # With the variable set the cache helper leaves JAX's config alone.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "[A]" in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_compile_cache_helper_uses_env_or_fixed_dir(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.configure_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
