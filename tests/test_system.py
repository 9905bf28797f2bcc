"""End-to-end behaviour tests: flash attention VJP, HLO analyzer, and the
full train/serve/solve paths through the public API."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.flash import flash_attention
from repro.models.layers import _direct_sdpa


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention_matches_reference(causal, window):
    key = jax.random.PRNGKey(0)
    B, S, K, G, hd = 2, 256, 2, 3, 32
    q = jax.random.normal(key, (B, S, K, G, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, hd), jnp.float32)

    o1 = flash_attention(q, k, v, causal, window, 64, 64)
    o2 = _direct_sdpa(q, k, v, causal=causal, window=window, q_offset=0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)

    def f(fn):
        return lambda *a: (fn(*a) ** 2).sum() + fn(*a).sum()

    gf = jax.grad(f(lambda *a: flash_attention(*a, causal, window, 64, 64)),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f(lambda *a: _direct_sdpa(*a, causal=causal, window=window,
                                            q_offset=0)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_hlo_analyzer_counts_loop_trips():
    """cost_analysis counts a scan body once; the analyzer must multiply by
    the known trip count (the roofline depends on this)."""
    from repro.launch.hlo_analysis import analyze
    d, L = 128, 6

    def f(params, x):
        def body(h, p):
            return jnp.tanh(h @ p), None
        h, _ = jax.lax.scan(body, x, params)
        return h.sum()

    co = jax.jit(f).lower(
        jax.ShapeDtypeStruct((L, d, d), jnp.float32),
        jax.ShapeDtypeStruct((d, d), jnp.float32)).compile()
    st = analyze(co.as_text())
    assert abs(st.flops - 2 * d ** 3 * L) / (2 * d ** 3 * L) < 0.05


def test_mesh_construction():
    """make_production_mesh shape contract (uses abstract mesh on 1 CPU)."""
    from repro.launch.mesh import abstract_mesh_compat
    devs = jax.devices()
    if len(devs) < 512:
        # AbstractMesh validates the same shape/axes contract
        m = abstract_mesh_compat((2, 16, 16), ("pod", "data", "model"))
        assert m.shape == {"pod": 2, "data": 16, "model": 16}
        m1 = abstract_mesh_compat((16, 16), ("data", "model"))
        assert m1.size == 256


def test_input_specs_cover_all_cells():
    from repro.configs import ARCHS, get_config
    from repro.launch.shapes import SHAPES, input_specs, shape_applicable
    cells = ok_cells = 0
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES:
            cells += 1
            applicable, why = shape_applicable(cfg, shape)
            if not applicable:
                assert shape == "long_500k" and not cfg.subquadratic
                continue
            specs = input_specs(cfg, shape)
            assert "batch" in specs
            ok_cells += 1
    assert cells == 40
    assert ok_cells == 32          # 8 long_500k cells skipped by design


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                         ("cpu", False), ("TPU v9", False)])
def test_peaks_are_keyed_by_device_kind(kind, known):
    """Roofline peaks come from a sourced per-kind table; an unknown
    device kind is an error, never the v5e default."""
    from repro.launch.peaks import peaks
    if known:
        assert peaks(kind)["hbm_bytes_s"] == 819e9
    else:
        with pytest.raises(KeyError, match="no published peaks"):
            peaks(kind)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """The entry points' compile cache is $JAX_COMPILATION_CACHE_DIR when
    set -- entries land there and nowhere else -- else the fixed
    <repo>/.jax_cache; importing the library leaves it off."""
    import os
    import subprocess
    import sys
    import textwrap
    from repro.launch.cache import DEFAULT_CACHE_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(repo, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = textwrap.dedent(f"""
        import os, jax, jax.numpy as jnp
        import repro.core
        assert (jax.config.jax_compilation_cache_dir
                == os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        from repro.launch.cache import enable_compile_cache
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        if {from_env}:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
        print(path)
    """)
    before = sorted(DEFAULT_CACHE_DIR.glob("*")) if \
        DEFAULT_CACHE_DIR.exists() else None
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    path = out.stdout.strip().splitlines()[-1]
    if from_env:
        assert path == str(tmp_path / "cc")
        assert any((tmp_path / "cc").iterdir())
        after = sorted(DEFAULT_CACHE_DIR.glob("*")) if \
            DEFAULT_CACHE_DIR.exists() else None
        assert after == before
    else:
        assert path == str(DEFAULT_CACHE_DIR)
        assert str(DEFAULT_CACHE_DIR.parent) == repo


def test_solver_config_registry():
    from repro.configs import ARCHS, get_config, get_reduced
    assert len(ARCHS) == 10
    for a in ARCHS:
        cfg = get_config(a)
        red = get_reduced(a)
        assert red.d_model < cfg.d_model


def test_end_to_end_train_launcher(tmp_path):
    from repro.launch.train import main
    params = main(["--arch", "mamba2-370m", "--reduced", "--steps", "2",
                   "--batch", "2", "--seq", "32",
                   "--ckpt-dir", str(tmp_path)])
    assert params is not None


def test_end_to_end_serve_launcher():
    from repro.launch.serve import main
    out = main(["--arch", "chatglm3-6b", "--reduced", "--batch", "2",
                "--prompt-len", "8", "--gen", "4"])
    assert out.shape == (2, 4)
