"""Multi-device semantics, run in subprocesses (the main pytest process
must keep the default 1-CPU-device view; XLA locks device count at init).

Covers: shuffle conservation + term-ownership, context-parallel attention
== single-device attention, distributed index step, debug-mesh train step,
and gradient equivalence of the sharded vs unsharded LM step.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 8, timeout: int = 900):
    env_code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n}'\n"
        # Auto axis types: the models constrain shardings with
        # with_sharding_constraint, which Explicit axes (the default of
        # jax.make_mesh) refuse
        "from jax.sharding import AxisType\n"
        "AUTO1, AUTO2 = (AxisType.Auto,), (AxisType.Auto,) * 2\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", env_code + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout,
        # JAX_PLATFORMS=cpu: these tests want n *virtual host* devices;
        # without it jax probes for real accelerators first, which on
        # images that ship libtpu means a minute of metadata lookups and,
        # depending on how they fail, a broken backend instead of the
        # CPU fallback.
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_spmd_regions_import_the_compat_shim():
    """Every SPMD region takes ``shard_map`` from one stable place, the
    installed JAX's top-level ``jax.shard_map`` — never the retired
    ``jax.experimental.shard_map`` module. Lint, not runtime: grep the
    source tree."""
    bad = []
    for path in sorted((REPO / "src").rglob("*.py")):
        for ln, line in enumerate(path.read_text().splitlines(), 1):
            code = line.split("#", 1)[0]
            if "jax.experimental.shard_map" in code or \
                    ("shard_map" in code and "import" in code
                     and "jax.experimental" in code):
                bad.append(f"{path.relative_to(REPO)}:{ln}: {line.strip()}")
    assert not bad, \
        "use jax.shard_map, not jax.experimental:\n" + "\n".join(bad)


def test_shuffle_conservation_and_ownership():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.shuffle import invert_and_shuffle
        mesh = jax.make_mesh((8,), ("model",), axis_types=AUTO1)
        D_per, L, V = 8, 24, 71
        rng = np.random.default_rng(2)
        tokens = rng.integers(0, V, size=(64, L)).astype(np.int32)
        def step(toks):
            def fn(t):
                idx = jax.lax.axis_index("model")
                run, stats = invert_and_shuffle(t, idx * D_per,
                                                axis_name="model", n_dest=8)
                return jax.tree.map(lambda x: x[None] if x.ndim == 0 else x,
                                    (run, stats))
            return shard_map(fn, mesh=mesh, in_specs=P("model", None),
                             out_specs=P("model"), check_vma=False)(toks)
        run, stats = jax.jit(step)(jnp.asarray(tokens))
        assert np.asarray(stats.dropped).sum() == 0
        assert np.asarray(stats.sent).sum() == (tokens > 0).sum()
        assert np.asarray(stats.recv).sum() == (tokens > 0).sum()
        terms = np.asarray(run.terms_unique).reshape(8, -1)
        nt = np.asarray(run.n_terms)
        for m in range(8):
            tt = terms[m][:nt[m]]
            assert (tt % 8 == m).all(), m
        print("SHUFFLE-OK")
    """)
    assert "SHUFFLE-OK" in out


def test_cp_attention_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.models.transformer import (MeshInfo, forward_train,
                                              init_params)
        cfg = get_arch("gemma2-9b").smoke
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        B, S = 4, 64
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1),
                 "mask": jnp.ones((B, S))}
        l_single, _ = forward_train(params, batch, cfg, MeshInfo())
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        mi = MeshInfo(mesh=mesh, dp_axes=("data",))
        with mesh:
            l_dist, _ = jax.jit(
                lambda p, b: forward_train(p, b, cfg, mi))(params, batch)
        np.testing.assert_allclose(float(l_single), float(l_dist),
                                   rtol=2e-3)
        print("CP-ATTN-OK", float(l_single), float(l_dist))
    """)
    assert "CP-ATTN-OK" in out


def test_distributed_grads_match_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.models.transformer import (MeshInfo, forward_train,
                                              init_params)
        cfg = get_arch("qwen3-32b").smoke
        key = jax.random.PRNGKey(1)
        params = init_params(key, cfg)
        B, S = 4, 64
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1),
                 "mask": jnp.ones((B, S))}
        g1 = jax.grad(lambda p: forward_train(p, batch, cfg, MeshInfo())[0])(params)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        mi = MeshInfo(mesh=mesh, dp_axes=("data",))
        with mesh:
            g2 = jax.jit(jax.grad(
                lambda p: forward_train(p, batch, cfg, mi)[0]))(params)
        n1 = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32)**2)
                          for x in jax.tree.leaves(g1)))
        n2 = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32)**2)
                          for x in jax.tree.leaves(g2)))
        np.testing.assert_allclose(float(n1), float(n2), rtol=5e-3)
        print("GRADS-OK", float(n1), float(n2))
    """)
    assert "GRADS-OK" in out


def test_distributed_index_step_compiles_and_runs():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.core.indexer import make_index_step
        cfg = get_arch("lucene-envelope").smoke
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        step = make_index_step(cfg, mesh, doc_len=cfg.doc_len)
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 1 << cfg.vocab_bits,
                              size=(8 * cfg.docs_per_shard, cfg.doc_len)
                              ).astype(np.int32)
        with mesh:
            out = jax.jit(step)(jnp.asarray(tokens))
        stats = out["stats"]
        assert np.asarray(stats.dropped).sum() == 0
        assert np.asarray(stats.sent).sum() == (tokens > 0).sum()
        assert float(np.asarray(out["packed_bytes"]).sum()) > 0
        print("INDEX-STEP-OK")
    """)
    assert "INDEX-STEP-OK" in out


def test_packed2_shuffle_parity():
    """The optimized shuffle payload (packed2 + single-key stable sort)
    must be bit-identical to the raw 3-word path (§Perf iteration 5)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.shuffle import invert_and_shuffle
        mesh = jax.make_mesh((8,), ("model",), axis_types=AUTO1)
        D_per, L, V = 16, 32, 97
        rng = np.random.default_rng(2)
        tokens = rng.integers(0, V, size=(128, L)).astype(np.int32)
        def run_mode(payload, sk):
            def step(toks):
                def fn(t):
                    idx = jax.lax.axis_index("model")
                    run, stats = invert_and_shuffle(
                        t, idx * D_per, axis_name="model", n_dest=8,
                        payload=payload, single_key_sort=sk)
                    return jax.tree.map(
                        lambda x: x[None] if x.ndim == 0 else x, (run, stats))
                return shard_map(fn, mesh=mesh, in_specs=P("model", None),
                                 out_specs=P("model"), check_vma=False)(toks)
            return jax.jit(step)(jnp.asarray(tokens))
        r1, s1 = run_mode("raw", False)
        r2, s2 = run_mode("packed2", True)
        assert np.asarray(s2.dropped).sum() == 0
        for f in r1._fields:
            assert (np.asarray(getattr(r1, f)) ==
                    np.asarray(getattr(r2, f))).all(), f
        print("PACKED2-OK")
    """)
    assert "PACKED2-OK" in out


def test_shard_map_moe_parity():
    """shard_map MoE (local dispatch + psum combine) == pjit MoE ==
    single device, at dropless capacity (§Perf iteration 4)."""
    out = run_with_devices("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.models.transformer import (MeshInfo, forward_train,
                                              init_params)
        base = get_arch("moonshot-v1-16b-a3b").smoke
        cfg_p = dataclasses.replace(base, capacity_factor=8.0)
        cfg_s = dataclasses.replace(base, capacity_factor=8.0,
                                    moe_impl="shard_map")
        key = jax.random.PRNGKey(2)
        params = init_params(key, cfg_p)
        B, S = 4, 32
        tokens = jax.random.randint(key, (B, S), 0, base.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1),
                 "mask": jnp.ones((B, S))}
        l0, _ = forward_train(params, batch, cfg_p, MeshInfo())
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        mi = MeshInfo(mesh=mesh, dp_axes=("data",))
        with mesh:
            l1, _ = jax.jit(lambda p, b: forward_train(p, b, cfg_p, mi))(params, batch)
            l2, _ = jax.jit(lambda p, b: forward_train(p, b, cfg_s, mi))(params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)
        np.testing.assert_allclose(float(l0), float(l2), rtol=2e-3)
        print("SM-MOE-OK")
    """)
    assert "SM-MOE-OK" in out


def test_moe_dispatch_parity_across_mesh():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_arch
        from repro.models.transformer import (MeshInfo, forward_train,
                                              init_params)
        cfg = get_arch("moonshot-v1-16b-a3b").smoke
        key = jax.random.PRNGKey(2)
        params = init_params(key, cfg)
        B, S = 4, 32
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1),
                 "mask": jnp.ones((B, S))}
        l1, _ = forward_train(params, batch, cfg, MeshInfo())
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        mi = MeshInfo(mesh=mesh, dp_axes=("data",))
        with mesh:
            l2, _ = jax.jit(lambda p, b: forward_train(p, b, cfg, mi))(params, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)
        print("MOE-OK", float(l1), float(l2))
    """)
    assert "MOE-OK" in out
