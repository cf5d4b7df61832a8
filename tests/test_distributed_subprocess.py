"""Distributed behaviors that need >1 device: run in a subprocess with
forced host devices (the main pytest process must keep 1 device)."""
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=420,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_topk_matches_oracle():
    print(_run("""
        import jax, jax.numpy as jnp
        from repro.index.sharded import sharded_cosine_topk
        from repro.kernels.simsearch.ref import simsearch_ref
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (5, 16))
        c = jax.random.normal(jax.random.fold_in(key, 1), (256, 16))
        with mesh:
            v, i = jax.jit(lambda a, b: sharded_cosine_topk(
                a, b, mesh, k=3))(q, c)
        vr, ir = simsearch_ref(q, c, 3)
        assert bool(jnp.all(i == ir)), (i, ir)
        assert float(jnp.max(jnp.abs(v - vr))) < 1e-5
        print("ok")
    """))


def test_sharded_ivf_topk_matches_flat_oracle():
    """Per-shard IVF scan + k-candidate merge: with full probing the
    merged result must equal exact flat search over the whole corpus
    (the sharded twin of the rerank-exactness argument, DESIGN.md §11)."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.index.sharded import (build_sharded_ivf,
                                         sharded_ivf_topk)
        from repro.index.flat import l2_normalize
        from repro.kernels.simsearch.ref import simsearch_ref
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(3)
        N, d = 4096, 32
        centers = rng.normal(size=(64, d))
        corpus = (centers[rng.integers(0, 64, N)]
                  + 0.3 * rng.normal(size=(N, d))).astype(np.float32)
        q = (corpus[rng.choice(N, 9)]
             + 0.05 * rng.normal(size=(9, d))).astype(np.float32)
        sivf = build_sharded_ivf(corpus, 4, n_clusters=16, iters=4)
        with mesh:
            v, i = jax.jit(lambda qq: sharded_ivf_topk(
                qq, sivf, mesh, k=3, nprobe=16, n_candidates=64))(
                    jnp.asarray(q))
        cn = np.asarray(l2_normalize(jnp.asarray(corpus)))
        vr, ir = simsearch_ref(q, cn, 3)
        assert bool(jnp.all(i == ir)), (i, ir)
        assert float(jnp.max(jnp.abs(v - vr))) < 1e-5
        print("ok")
    """))


def test_local_candidate_retrieval_matches_reference():
    print(_run("""
        import jax, jax.numpy as jnp
        from repro.index.sharded import sharded_topk_local_candidates
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        key = jax.random.PRNGKey(1)
        V, d, N, k = 64, 8, 32, 5
        table = jax.random.normal(key, (V, d))
        u = jax.random.normal(jax.random.fold_in(key, 1), (2, d))
        # range-partitioned candidate ids: shard s owns rows [s*16,(s+1)*16)
        ids = jnp.concatenate(
            [jnp.arange(s * 16, s * 16 + 8) for s in range(4)])
        with mesh:
            v, gi = jax.jit(lambda u, t, i: sharded_topk_local_candidates(
                u, t, i, mesh, k=k))(u, table, ids)
        cand = table[ids]
        ref = jnp.einsum("bd,nd->bn", u, cand)
        rv, ri = jax.lax.top_k(ref, k)
        assert float(jnp.max(jnp.abs(v - rv))) < 1e-5
        assert bool(jnp.all(gi == jnp.take(ids, ri)))
        print("ok")
    """))


def test_small_mesh_train_step_lowers_with_shardings():
    """End-to-end lowering of a (reduced) LM train step on a 2x4 mesh
    with the production sharding rules — the dry-run path in miniature."""
    print(_run("""
        import dataclasses, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.distributed import sharding as shd
        from repro.distributed.act_sharding import use_dp_axes
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as tr
        from repro.training import optimizer as opt
        cfg = dataclasses.replace(
            smoke_config("qwen3-1.7b"), d_model=64, n_heads=4,
            n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512)
        # the production sharding rules are GSPMD hints, which need the
        # Auto axes launch.mesh.make_mesh gives
        mesh = make_mesh((2, 4), ("data", "model"))
        ns = lambda s: NamedSharding(mesh, s)
        p_specs = shd.lm_param_specs(cfg)
        p_shard = jax.tree.map(ns, p_specs,
                               is_leaf=lambda x: isinstance(x, P))
        params = jax.eval_shape(lambda k: tr.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        opt_abs = jax.eval_shape(
            lambda p: opt.init(p, opt.AdamWConfig()), params)
        o_shard = {"mu": p_shard, "nu": p_shard, "master": p_shard,
                   "step": ns(P())}
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        b_shard = {k: ns(P(("data",), None)) for k in batch}
        step0 = opt.make_train_step(
            lambda p, b: tr.train_loss(cfg, p, b, vocab_chunk_seq=32),
            opt.AdamWConfig())
        def step(p, o, b):
            with use_dp_axes(("data",)):
                return step0(p, o, b)
        with mesh:
            c = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                        donate_argnums=(0, 1)).lower(
                params, opt_abs, batch).compile()
        assert c.cost_analysis() is not None
        print("compiled ok on", mesh.devices.size, "devices")
    """))


def test_checkpoint_elastic_reshard():
    """Save on one sharding, restore onto a different mesh shape."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import checkpoint as ck
        mesh1 = jax.make_mesh((8,), ("data",))
        mesh2 = jax.make_mesh((2, 4), ("data", "model"))
        x = jnp.arange(64.0).reshape(8, 8)
        t = {"w": jax.device_put(x, NamedSharding(mesh1, P("data")))}
        with tempfile.TemporaryDirectory() as d:
            ck.save(d, 1, t)
            out = ck.restore(d, 1, t, shardings={
                "w": NamedSharding(mesh2, P("data", "model"))})
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
        assert len(out["w"].sharding.device_set) == 8
        print("ok")
    """))
