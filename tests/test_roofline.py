"""HLO cost parser: analytic validation on real lowered modules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analysis
from repro.roofline.hlo_parse import analyze, parse_module


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_single_dot_flops():
    a = jnp.zeros((128, 256), jnp.float32)
    b = jnp.zeros((256, 64), jnp.float32)
    c = _compile(lambda a, b: a @ b, a, b)
    costs = analyze(c.as_text(), 1)
    expect = 2 * 128 * 256 * 64
    assert abs(costs.flops - expect) / expect < 0.01


def test_scan_multiplies_by_trip_count():
    """A matmul inside lax.scan must count trip_count times."""
    w = jnp.zeros((8, 64, 64), jnp.float32)
    x = jnp.zeros((4, 64), jnp.float32)

    def fn(w, x):
        def body(c, wi):
            return c @ wi, ()
        out, _ = jax.lax.scan(body, x, w)
        return out

    c = _compile(fn, w, x)
    costs = analyze(c.as_text(), 1)
    expect = 8 * 2 * 4 * 64 * 64
    assert costs.flops >= 0.95 * expect, (costs.flops, expect)
    assert costs.flops <= 1.6 * expect
    assert any(t == 8 for t in costs.loop_trip_counts.values()), \
        costs.loop_trip_counts


def test_train_step_flops_match_analytic():
    """Full reduced train step: parsed flops ~= 8*N*D (fwd 2ND + bwd 4ND +
    full-remat re-forward 2ND) within attention/einsum slack."""
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_host_mesh
    from repro.launch.shapes import ShapeCell, input_specs
    from repro.models.registry import get_config
    from repro.models.transformer import LM
    from repro.optim import adamw
    from repro.train.steps import abstract_train_state, build_train_step

    cfg = get_config("qwen2-0.5b").reduced()
    model = LM(cfg)
    mesh = make_host_mesh()
    rules = ShardingRules.default()
    opt = adamw(1e-3)
    shape = ShapeCell("tiny", 64, 4, "train")
    with mesh:
        step = build_train_step(model, opt, mesh, rules)
        st = abstract_train_state(model, opt, rules, mesh)
        batch = input_specs(cfg, shape, rules, mesh)
        compiled = jax.jit(step, donate_argnums=0).lower(st, batch).compile()
    costs = analyze(compiled.as_text(), 1)
    analytic = 8 * model.param_count() * 4 * 64
    assert 0.8 * analytic < costs.flops < 2.0 * analytic, \
        (costs.flops, analytic)
    assert costs.hbm_bytes > 0


def test_collective_parse_allreduce():
    """psum on an 8-device mesh -> all-reduce with ring-model bytes."""
    import subprocess
    import sys
    import os
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.roofline.hlo_parse import analyze
from jax import shard_map
mesh = make_mesh((8,), ("x",))
def f(a):
    return jax.lax.psum(a, "x")
g = shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P(),
                  check_vma=False)
c = jax.jit(g).lower(jnp.zeros((8, 1024), jnp.float32)).compile()
costs = analyze(c.as_text(), 8)
assert costs.collective_counts.get("all-reduce", 0) >= 1, costs.collective_counts
# result bytes per shard = 1024 floats = 4096B; ring all-reduce ~ 2*(7/8)*4096
assert 4096 < costs.link_bytes < 4 * 4096, costs.link_bytes
print("COLLECTIVE_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd="/root/repo", timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "COLLECTIVE_OK" in r.stdout


def test_roofline_terms_bounds():
    class C:
        flops = 197e12          # exactly 1s of compute per chip
        hbm_bytes = 819e9 / 2   # 0.5s of HBM
        link_bytes = 50e9 / 4   # 0.25s of link
    terms = analysis.compute_terms_from_costs(C, 256, 197e12 * 256)
    assert terms.bound == "compute"
    assert abs(terms.compute_s - 1.0) < 1e-6
    assert abs(terms.roofline_fraction - 1.0) < 1e-6
