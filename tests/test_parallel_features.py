"""Distributed features on 8 fake devices (subprocess): sketched gradient
compression, GPipe pipeline over a mesh axis, elastic checkpoint restore,
parameter sharding rules."""

from dist_helper import run_distributed


def test_grad_compression_reduces_comm_and_converges():
    run_distributed(r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core.compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.parallel.grad_compress import (compress_and_allreduce,
    init_error_fb, comm_words_exact, comm_words_compressed)
from repro.roofline.hlo import collective_bytes_of

mesh = Mesh(np.asarray(jax.devices()), ("data",))
D, H = 64, 128
key = jax.random.key(0)
# low-rank target: rank-8 compression can represent the full gradient
U = jax.random.normal(key, (D, 4))
V = jax.random.normal(jax.random.fold_in(key, 1), (4, H))
W_true = U @ V / 2

def loss_fn(params, x):
    y = x @ W_true
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)

params = {"w": jnp.zeros((D, H))}
from repro.parallel.grad_compress import local_fb, stack_fb
fb = init_error_fb(params, rank=8, min_dim=16, world=8)  # per-worker state

def step(params, fb, x, t):
    g = jax.grad(loss_fn)(params, x)
    g, fb_l = compress_and_allreduce(g, local_fb(fb), step=t, rank=8,
                                     min_dim=16, axis_name="data")
    params = jax.tree_util.tree_map(lambda p, gg: p - 20.0 * gg, params, g)
    return params, stack_fb(fb_l)

sfn = shard_map(step, mesh=mesh,
                    in_specs=(P(), P("data"), P("data"), P()),
                    out_specs=(P(), P("data")), check_vma=False)
sfn = jax.jit(sfn)

# comm volume: compressed HLO must move fewer collective bytes than psum
x0 = jax.random.normal(jax.random.key(1), (16, D))
comp = sfn.lower(params, fb, x0, jnp.int32(0)).compile()
cbytes = collective_bytes_of(comp.as_text()).total

def step_exact(params, x):
    g = jax.grad(loss_fn)(params, x)
    g = jax.lax.pmean(g, "data")
    return jax.tree_util.tree_map(lambda p, gg: p - 20.0 * gg, params, g)
exact = jax.jit(shard_map(step_exact, mesh=mesh,
                in_specs=(P(), P("data")), out_specs=P(),
                check_vma=False))
ebytes = collective_bytes_of(exact.lower(params, x0).compile().as_text()).total
assert cbytes < ebytes, (cbytes, ebytes)
print("comm bytes: compressed", cbytes, "exact", ebytes)

# words model agrees qualitatively
assert comm_words_compressed(params, 8, 16) < comm_words_exact(params)

# convergence with error feedback + trajectory match vs exact SGD
pe = {"w": jnp.zeros((D, H))}
losses = []
for t in range(300):
    x = jax.random.normal(jax.random.fold_in(key, t), (16 * 8, D))
    params, fb = sfn(params, fb, x, jnp.int32(t))
    pe = exact(pe, x)
    losses.append(float(loss_fn(params, x)))
assert losses[-1] < 0.01 * losses[0], (losses[0], losses[-1])
# rank-8 compression of a rank-4 problem reproduces exact DP-SGD
drift = float(jnp.abs(params["w"] - pe["w"]).max())
assert drift < 1e-3, drift
print("OK", losses[0], "->", losses[-1], "drift", drift)
""")


def test_compressed_equals_exact_at_full_rank():
    """With rank >= min(m, n), PowerSGD reconstructs the exact mean
    gradient (orthonormal basis spans the full row space)."""
    run_distributed(r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.grad_compress import compress_and_allreduce, init_error_fb

mesh = Mesh(np.asarray(jax.devices()), ("data",))
m, n = 24, 16
grads = {"w": jax.random.normal(jax.random.key(0), (8 * m, n))}

def body(g_local):
    fb = init_error_fb({"w": g_local}, rank=n, min_dim=4)
    out, _ = compress_and_allreduce({"w": g_local}, fb, step=jnp.int32(0),
                                    rank=n, min_dim=4, axis_name="data")
    exact = jax.lax.pmean(g_local, "data")
    return out["w"], exact

fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                   out_specs=(P(), P()), check_vma=False)
approx, exact = fn(grads["w"].reshape(8, m, n).reshape(8 * m, n))
err = float(jnp.abs(approx - exact).max())
assert err < 1e-4, err
print("OK", err)
""")


def test_pipeline_matches_sequential():
    run_distributed(r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.pipeline import pipeline

n_stages, M, B, D = 4, 8, 2, 16
mesh = Mesh(np.asarray(jax.devices()[:n_stages]), ("pod",))
Ws = jax.random.normal(jax.random.key(0), (n_stages, D, D)) * 0.3
x = jax.random.normal(jax.random.key(1), (M, B, D))

def stage_fn(w, h):
    return jnp.tanh(h @ w)

def run_pipe(ws_local, xq):
    return pipeline(stage_fn, ws_local[0], xq, axis="pod",
                    n_stages=n_stages)

fn = shard_map(run_pipe, mesh=mesh,
                   in_specs=(P("pod"), P()), out_specs=P(),
                   check_vma=False)
out = fn(Ws, x)

# sequential reference
ref = x
for s in range(n_stages):
    ref = stage_fn(Ws[s], ref)
err = float(jnp.abs(out - ref).max())
assert err < 1e-5, err

# HLO contains collective-permute (the stage handoff)
txt = jax.jit(fn).lower(Ws, x).compile().as_text()
assert "collective-permute" in txt
print("OK", err)
""", ndev=8)


def test_param_shardings_rules():
    run_distributed(r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core.compat import shard_map
from repro.configs import get_config
from repro.models import get_api
from repro.parallel.sharding import param_shardings
from repro.launch.mesh import make_production_mesh

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = get_config("llama3-8b")
api = get_api(cfg)
shapes = jax.eval_shape(lambda: api.init(jax.random.key(0), cfg))
sh = param_shardings(shapes, mesh)

def find(path_frag):
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    for p, s in flat:
        name = "/".join(str(getattr(x, 'key', x)) for x in p)
        if path_frag in name:
            return name, s
    raise KeyError(path_frag)

n, s = find("wq")
assert s.spec[-1] == "model", (n, s.spec)
n, s = find("wo")
assert s.spec[-2] == "model", (n, s.spec)
n, s = find("embed")
assert s.spec[0] == "model", (n, s.spec)   # vocab-sharded
n, s = find("w_down")
assert s.spec[-2] == "model", (n, s.spec)

# MoE: experts sharded
cfg2 = get_config("dbrx-132b")
shapes2 = jax.eval_shape(lambda: get_api(cfg2).init(jax.random.key(0), cfg2))
sh2 = param_shardings(shapes2, mesh)
flat = jax.tree_util.tree_flatten_with_path(sh2)[0]
moe_gate = [s for p, s in flat
            if "moe" in "/".join(str(getattr(x, 'key', x)) for x in p)
            and "w_gate" in "/".join(str(getattr(x, 'key', x)) for x in p)]
assert moe_gate and moe_gate[0].spec[1] == "model", moe_gate[0].spec
print("OK")
""")


def test_elastic_restore_across_meshes(tmp_path):
    run_distributed(r"""
import jax, jax.numpy as jnp
import numpy as np
import tempfile, os
from repro.configs import get_config
from repro.configs.base import RunConfig
from repro.models import get_api
from repro.train.step import init_state
from repro.checkpoint import ckpt
from repro.launch.elastic import elastic_restore, remesh, rescale_accum

cfg = get_config("llama3-8b").reduced(n_layers=2, d_model=64, d_ff=128,
                                      vocab=128, head_dim=16)
api = get_api(cfg)
run = RunConfig(steps=10)
state = init_state(api, cfg, run, jax.random.key(0))
d = tempfile.mkdtemp()
ckpt.save(d, 5, state)

# restore onto an 8-device (4x2) mesh
mesh8 = remesh(jax.devices(), dp=4, tp=2)
st8, step, _ = elastic_restore(d, state, mesh=mesh8)
assert step == 5

# "failure": restore the same checkpoint onto a 4-device (2x2) mesh
mesh4 = remesh(jax.devices()[:4], dp=2, tp=2)
st4, step, _ = elastic_restore(d, state, mesh=mesh4)
for a, b in zip(jax.tree_util.tree_leaves(st8.params),
                jax.tree_util.tree_leaves(st4.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# grad-accum rescaling preserves global batch
accum8, gb8 = rescale_accum(global_batch=256, per_device_batch=8, dp_size=4)
accum4, gb4 = rescale_accum(global_batch=256, per_device_batch=8, dp_size=2)
assert gb8 == gb4 == 256
assert accum4 == 2 * accum8
print("OK")
""")


def test_compat_vmem_scratch_probe():
    """core/compat.py is two plain aliases for the one installed JAX:
    ``shard_map`` IS ``jax.shard_map`` and ``vmem_scratch`` IS
    ``pltpu.VMEM`` — no version probe, no fallback.  The allocation below
    must round-trip its shape for pallas_call scratch_shapes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import compat

    assert compat.shard_map is jax.shard_map
    assert compat.vmem_scratch is pltpu.VMEM
    scratch = compat.vmem_scratch((8, 128), jnp.float32)
    assert tuple(scratch.shape) == (8, 128)
