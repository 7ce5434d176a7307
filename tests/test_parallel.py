"""Sharding rules + MoE distribution + HLO cost walker."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.parallel.sharding import (batch_spec, data_axis_names,
                                     resolve_axes)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestLogicalRules:
    def test_tp_dims(self):
        # d_ff over model; d_model over data (FSDP)
        spec = resolve_axes(("embed", "ffn"), (5120, 25600), MESH)
        assert spec == P("data", "model")

    def test_kv_not_divisible_stays_replicated(self):
        # MQA: 1 kv head cannot shard over a 16-way model axis
        spec = resolve_axes(("embed", "kv", None), (2048, 1, 256), MESH)
        assert spec == P("data", None, None)

    def test_heads_divisible(self):
        spec = resolve_axes(("embed", "heads", None), (5120, 64, 128), MESH)
        assert spec == P("data", "model", None)

    def test_multi_pod_fsdp_axes(self):
        spec = resolve_axes(("embed", "ffn"), (5120, 25600), MESH3)
        assert spec == P(("pod", "data"), "model")

    def test_embed_not_divisible(self):
        # 100 doesn't divide by 16 -> replicated rather than invalid
        spec = resolve_axes(("embed",), (100,), MESH)
        assert spec == P(None)

    def test_one_mesh_axis_used_once(self):
        # vocab and heads can't both take 'model'
        spec = resolve_axes(("vocab", "heads"), (512, 64), MESH)
        assert spec == P("model", None)

    def test_layers_dim_replicated(self):
        spec = resolve_axes(("layers", "embed", "ffn"), (64, 5120, 1024),
                            MESH)
        assert spec == P(None, "data", "model")

    def test_batch_spec(self):
        assert batch_spec(MESH) == P("data")
        assert batch_spec(MESH3) == P(("pod", "data"))
        assert data_axis_names(MESH3) == ("pod", "data")


class TestMoEDispatch:
    def _setup(self, t=64, d=16, e=8, k=2, cap=4):
        from repro.models.moe import _dispatch_compute, _route
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
        router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
        w_g = jnp.asarray(rng.standard_normal((e, d, 8)) * 0.1, jnp.float32)
        w_u = jnp.asarray(rng.standard_normal((e, d, 8)) * 0.1, jnp.float32)
        w_d = jnp.asarray(rng.standard_normal((e, 8, d)) * 0.1, jnp.float32)
        return x, router, (w_g, w_u, w_d)

    def test_sharded_expert_partition_sums_to_full(self):
        """Sum of per-shard partial outputs == single-shard full output."""
        from repro.models.moe import _dispatch_compute, _route
        from repro.configs.base import MoEConfig
        x, router, (w_g, w_u, w_d) = self._setup()
        m = MoEConfig(n_experts=8, top_k=2, d_expert=8, capacity_factor=16.0)
        idx, gate, aux = _route(x, router, m)
        cap = 64  # no drops
        full = _dispatch_compute(x, idx, gate, w_g, w_u, w_d, 0, 8, cap)
        part = sum(
            _dispatch_compute(x, idx, gate, w_g[lo:lo + 2], w_u[lo:lo + 2],
                              w_d[lo:lo + 2], lo, 2, cap)
            for lo in range(0, 8, 2))
        np.testing.assert_allclose(np.asarray(part), np.asarray(full),
                                   rtol=2e-4, atol=2e-5)

    def test_capacity_drops_tokens(self):
        from repro.models.moe import _dispatch_compute, _route
        from repro.configs.base import MoEConfig
        x, router, (w_g, w_u, w_d) = self._setup()
        m = MoEConfig(n_experts=8, top_k=2, d_expert=8)
        idx, gate, _ = _route(x, router, m)
        tiny = _dispatch_compute(x, idx, gate, w_g, w_u, w_d, 0, 8, 1)
        big = _dispatch_compute(x, idx, gate, w_g, w_u, w_d, 0, 8, 64)
        # capacity 1 must zero-out some tokens' contributions
        assert float(jnp.abs(tiny - big).max()) > 0

    def test_router_normalizes_gates(self):
        from repro.models.moe import _route
        from repro.configs.base import MoEConfig
        x, router, _ = self._setup()
        m = MoEConfig(n_experts=8, top_k=2, d_expert=8)
        idx, gate, aux = _route(x, router, m)
        np.testing.assert_allclose(np.asarray(gate.sum(-1)), 1.0, rtol=1e-5)
        assert float(aux) > 0


class TestHloCostWalker:
    """Ground-truth validation in a subprocess (needs >1 fake device)."""

    SCRIPT = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_cost import analyze_hlo
        mesh = jax.make_mesh((4, 4), ("data", "model"))
        w1 = jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16)
        w2 = jax.ShapeDtypeStruct((1024, 512), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((64, 512), jnp.bfloat16)
        def f(x, w1, w2):
            def body(c, _):
                return jnp.maximum(c @ w1, 0) @ w2, ()
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out
        sh = lambda *s: NamedSharding(mesh, P(*s))
        jf = jax.jit(f, in_shardings=(sh("data", None), sh(None, "model"),
                                      sh("model", None)),
                     out_shardings=sh("data", None))
        res = analyze_hlo(jf.lower(x, w1, w2).compile().as_text())
        # per-device truth: 7 iters x 2 dots x 2*16*512*256-ish partitions
        expect = 7 * 2 * (2 * 64 * 512 * 1024) / 16
        assert abs(res["dot_flops"] - expect) / expect < 0.01, res
        assert res["collective_bytes"].get("all-reduce", 0) > 0
        print("WALKER_OK", res["dot_flops"])
    """)

    def test_walker_ground_truth(self):
        out = subprocess.run([sys.executable, "-c", self.SCRIPT],
                             capture_output=True, text=True, timeout=300,
                             cwd=str(__import__("pathlib").Path(
                                 __file__).parent.parent))
        assert "WALKER_OK" in out.stdout, out.stderr[-2000:]

    def test_parser_handles_index_comments(self):
        from repro.launch.hlo_cost import HloModule
        txt = """ENTRY %main.1 (p0: f32[4,4], /*index=1*/p1: f32[4,4]) -> f32[4,4] {
  %p0 = f32[4,4]{1,0} parameter(0)
  %p1 = f32[4,4]{1,0} parameter(1)
  ROOT %dot.1 = f32[4,4]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}"""
        res = HloModule(txt).analyze()
        assert res["dot_flops"] == 2 * 4 * 4 * 4

    def test_trip_count_attr_preferred(self):
        from repro.launch.hlo_cost import HloModule
        txt = """%body.1 (p: (s32[], f32[2,2])) -> (s32[], f32[2,2]) {
  %p = (s32[], f32[2,2]{1,0}) parameter(0)
  %a = f32[2,2]{1,0} get-tuple-element(%p), index=1
  %d = f32[2,2]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%cond.1 (p: (s32[], f32[2,2])) -> pred[] {
  %p = (s32[], f32[2,2]{1,0}) parameter(0)
  %c = s32[] constant(99)
}

ENTRY %main.2 (p0: (s32[], f32[2,2])) -> (s32[], f32[2,2]) {
  %p0 = (s32[], f32[2,2]{1,0}) parameter(0)
  ROOT %w = (s32[], f32[2,2]{1,0}) while(%p0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
}"""
        res = HloModule(txt).analyze()
        # known_trip_count=5 wins over the constant 99 in the condition
        assert res["dot_flops"] == 5 * 2 * 2 * 2 * 2
