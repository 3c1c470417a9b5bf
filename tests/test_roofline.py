"""Roofline machinery: HLO cost walker (loop multiplicity), byte models."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.roofline.hlo_bytes import collective_bytes, parse_collectives
from repro.roofline.hlo_cost import walk_hlo
from repro.roofline.model import (V5E, model_flops_train, roofline_terms)


def _compile(f, *specs, **jit_kw):
    return jax.jit(f, **jit_kw).lower(*specs).compile()


class TestWalker:
    def test_dot_flops_exact(self):
        c = _compile(lambda a, b: a @ b,
                     jax.ShapeDtypeStruct((64, 128), jnp.float32),
                     jax.ShapeDtypeStruct((128, 32), jnp.float32))
        w = walk_hlo(c.as_text())
        assert w.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.05)

    def test_scan_multiplicity(self):
        """A 13-iteration scan body counts ×13 — the cost_analysis bug
        this walker exists to fix."""
        def f(x):
            def body(c, _):
                return jnp.tanh(c @ c), None
            y, _ = jax.lax.scan(body, x, None, length=13)
            return y

        c = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
        w = walk_hlo(c.as_text())
        assert w.flops == pytest.approx(13 * 2 * 64 ** 3, rel=0.05)
        assert w.transcendentals == pytest.approx(13 * 64 * 64, rel=0.01)
        ca = c.cost_analysis()          # dict (new jax) or [dict] (old jax)
        xla = dict(ca[0] if isinstance(ca, (list, tuple)) else ca)
        assert xla["flops"] < w.flops / 5       # the bug being fixed

    def test_nested_scans_multiply(self):
        def f(x):
            def outer(c, _):
                def inner(d, _):
                    return d @ d, None
                d, _ = jax.lax.scan(inner, c, None, length=3)
                return d, None
            y, _ = jax.lax.scan(outer, x, None, length=5)
            return y

        c = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
        w = walk_hlo(c.as_text())
        assert w.flops == pytest.approx(15 * 2 * 32 ** 3, rel=0.1)

    def test_bytes_scale_with_loops(self):
        def f(x):
            def body(c, _):
                return c * 2.0 + 1.0, None
            y, _ = jax.lax.scan(body, x, None, length=10)
            return y

        c = _compile(f, jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
        w = walk_hlo(c.as_text())
        # ≥ 10 × (read + write) of 4 MB
        assert w.hbm_bytes >= 10 * 2 * 4 * 1024 * 1024 * 0.9


class TestCollectiveModel:
    def test_parse_and_byte_model(self):
        hlo = """
ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %ar = f32[64,256]{1,0} all-reduce(%x), replica_groups=[4,2]<=[8]
  %ag = f32[64,256]{1,0} all-gather(%y), replica_groups=[2,4]<=[8]
  %cp = f32[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
}
"""
        ops = parse_collectives(hlo, default_group=8)
        assert len(ops) == 3
        ar, ag, cp = ops
        rb = 64 * 256 * 4
        assert ar.kind == "all-reduce" and ar.group_size == 2
        assert ar.wire_bytes == int(2 * 0.5 * rb)
        assert ag.group_size == 4
        assert ag.wire_bytes == int(0.75 * rb)
        assert cp.wire_bytes == 8 * 8 * 4
        agg = collective_bytes(hlo, 8)
        assert agg["n_ops"] == 3

    def test_real_allreduce_counted(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if jax.device_count() < 2:
            pytest.skip("single-device session")
        mesh = make_mesh((jax.device_count(),), ("d",))
        s = NamedSharding(mesh, P(None, "d"))
        c = _compile(lambda a, b: a @ b,
                     jax.ShapeDtypeStruct((32, 64), jnp.float32),
                     jax.ShapeDtypeStruct((64, 32), jnp.float32),
                     in_shardings=(s, NamedSharding(mesh, P("d", None))),
                     out_shardings=NamedSharding(mesh, P()))
        w = walk_hlo(c.as_text(), default_group=jax.device_count())
        assert w.collective_count >= 1 and w.wire_bytes > 0


class TestModel:
    def test_terms_and_dominance(self):
        t = roofline_terms({"flops": 197e12, "bytes accessed": 819e9},
                           wire_bytes=0.0)
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(1.0)
        assert t.dominant in ("compute", "memory")
        t2 = roofline_terms({"flops": 1.0, "bytes accessed": 1.0},
                            wire_bytes=200e9 * 10)
        assert t2.dominant == "collective"
        assert t2.collective_s == pytest.approx(10.0)

    def test_useful_fraction(self):
        t = roofline_terms({"flops": 1e12, "bytes accessed": 1.0},
                           wire_bytes=0.0, chips=256,
                           model_flops=128e12)
        assert t.useful_fraction == pytest.approx(0.5)

    def test_v5e_constants(self):
        assert V5E.peak_bf16_flops == 197e12
        assert V5E.hbm_bw == 819e9
        assert V5E.ici_link_bw == 50e9
        assert model_flops_train(1e9, 1e6) == 6e15


class TestDryrunArtifacts:
    """Validate the committed dry-run artifacts if present."""

    def test_single_pod_artifacts(self):
        import json
        import os
        d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                         "dryrun", "single")
        if not os.path.isdir(d):
            pytest.skip("dry-run artifacts not generated yet")
        recs = [json.load(open(os.path.join(d, f)))
                for f in os.listdir(d) if f.endswith(".json")]
        assert len(recs) >= 30
        for r in recs:
            assert r["status"] == "OK", r
            assert r["chips"] == 256
            t = r["roofline"]
            assert t["compute_s"] > 0 and t["memory_s"] > 0
            assert r["fits_hbm"], (r["arch"], r["shape"],
                                   r["memory"]["total_bytes"])
