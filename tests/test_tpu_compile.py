"""The Pallas kernels compile for a TPU v5e, at the chip smoke's widths,
under their names.

Interpret mode on the CPU cannot see what the TPU compiler refuses: VMEM
over the scoped limit, an unaligned dynamic slice, an unsupported cast.
These tests compile each kernel of the main path for one chip of a
described (not attached) ``v5e:2x2`` topology and assert that the compiled
program calls the Mosaic kernel (``tpu_custom_call``) and that each
kernel's call carries its ``pallas_call`` name (the device op's name in
a profile).  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers import every test file.  The fixture skips when no
topology can be described (no TPU compiler installed) and turns JAX's
persistent compilation cache off, since a program compiled for a described
chip is written to it but cannot be read back without one.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import local, ops
from repro.kernels.sketch_matmul import (
    PANEL_VMEM_BUDGET, STEP_VMEM, panel_bytes,
)

# the chip smoke's per-chip widths (chip_smoke.py)
N, R = 32768, 256                  # dense sketch / Nyström: A is N x N
N1, N2, R_S, L_S = 4096, 768, 48, 97   # one stream tenant (l = 2r + 1)
LANES, KB = 64, 64                 # a full ragged bucket


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    """name -> (fn, argument shapes); every kernel call native
    (``interpret=False``)."""
    def sketch(kind, acc):
        def fn(a, *y):
            return local.sketch_block(a, 0, R, kind=kind, backend="pallas",
                                      acc=y[0] if acc else None,
                                      interpret=False)
        args = [((N, N), jnp.float32)] + ([((N, R), jnp.float32)]
                                          if acc else [])
        return fn, args

    def fold(y, d, s, n):
        return local.fold_rows_block(y, d, s, backend="pallas", nvalid=n,
                                     interpret=False)

    i32 = jnp.int32
    return {
        "sketch_block-normal": sketch("normal", False),
        "sketch_block-normal-acc": sketch("normal", True),
        "sketch_block-uniform": sketch("uniform", False),
        "sketch_block-uniform-acc": sketch("uniform", True),
        # an Omega tile of one generator slice: the fill loop runs once
        "sketch_block-one-slice": (
            lambda a: local.sketch_block(a, 0, R_S, backend="pallas",
                                         interpret=False),
            [((KB, 64), jnp.float32)]),
        "sketch_t_block": (
            lambda b: local.sketch_t_block(b, 0, R, backend="pallas",
                                           interpret=False),
            [((N, R), jnp.float32)]),
        "gemm_block-acc": (
            lambda a, b, y: local.gemm_block(a, b, acc=y, alpha=-1.0,
                                             backend="pallas",
                                             interpret=False),
            [((N1, R_S), jnp.float32), ((R_S, N2), jnp.float32),
             ((N1, N2), jnp.float32)]),
        "fold_rows_block-masked": (
            fold, [((N1, R_S), jnp.float32), ((KB, R_S), jnp.float32),
                   ((), i32), ((), i32)]),
        "fold_rows_block-vmapped": (
            jax.vmap(fold),
            [((LANES, N1, R_S), jnp.float32),
             ((LANES, KB, R_S), jnp.float32), ((LANES,), i32),
             ((LANES,), i32)]),
        "ops.sketch_matmul": (
            lambda a: ops.sketch_matmul(a, seed=0, r=R),
            [((N, N), jnp.float32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases()[name]
    shapes = [_shape(one_chip, s, dt) for s, dt in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, name


def test_default_blocks_fit_the_scoped_vmem():
    """The native block policy's tiles fit the budget by the same model
    the autotuner filters with, and the budget sits under the limit the
    kernels ask Mosaic for."""
    assert local.VMEM_BUDGET <= local.VMEM_LIMIT
    for m, n, k in ((N, R, N), (R, R, N), (N1, R_S, N2), (KB, R_S, N2)):
        bm, bn, bk = local.default_local_blocks(m, n, k, interpret=False)
        assert local.vmem_fit_bytes(bm, bn, bk) <= local.VMEM_BUDGET
        sk = local.gen_rows(bk, max(bm, bn))
        assert bk % sk == 0 and sk % 8 == 0


# sketch_a_omega at r = R: case -> (rows of A, its contraction, whether the
# kernel keeps its (n2, 128) f32 Omega panel in VMEM)
_OMEGA_PANEL = {
    "dense32k": (N, N, True),                  # a 16 MiB panel
    "dense56k": (57344, 57344, True),          # a 28 MiB panel
    "long-contraction": (512, 262144, False),  # 128 MiB: over the budget
}


@pytest.mark.parametrize("case", sorted(_OMEGA_PANEL))
def test_sketch_a_omega_compiles_on_its_path(one_chip, case):
    """The launch picks the panel path from the shape, the kernel
    compiles under the scoped VMEM it asks for, and that is the step's
    share plus the panel, within the budget."""
    n1, n2, panel = _OMEGA_PANEL[case]
    assert ops.sketch_matmul_launch(n1, n2, R).panel == panel
    text = jax.jit(lambda a: ops.sketch_matmul(a, seed=0, r=R)).lower(
        _shape(one_chip, (n1, n2))).compile().as_text()
    (line,) = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    asked = int(re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                          line).group(1))
    want = STEP_VMEM + (panel_bytes(n2, 128) if panel else 0)
    assert asked == want <= PANEL_VMEM_BUDGET, (asked, want)


def test_nystrom_fused_compiles_at_dense56k(one_chip):
    """The chip-filling pair (A 57344^2 f32, 82% of HBM): stage 1 on its
    panel path, stage 2 covering B with one column block, so generating
    each Omega tile once, within the default VMEM scope, and no copy of
    A: temporaries far below A's size."""
    n = 57344
    assert ops.sketch_matmul_launch(n, n, R).panel
    assert ops.sketch_t_matmul_launch(n, R, R).generated == n * R
    compiled = jax.jit(lambda a: ops.nystrom_fused(
        a, seed=20260316, r=R, bm=256, bn=128, bk=512)).lower(
            _shape(one_chip, (n, n))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * n * n
    assert mem.output_size_in_bytes == pytest.approx(4 * (n * R + R * R),
                                                     rel=1e-4)   # + tuple
    assert mem.temp_size_in_bytes < 2 ** 20
    lines = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    calls = [re.search(r"%(\w+)", ln).group(1) for ln in lines]
    assert calls == ["sketch_a_omega", "sketch_omega_t_b"], calls
    # stage 2, B's (512, 256) block at full width, asks for no more than
    # the default scope, and its compiled step uses less than that
    stage2 = lines[1]
    asked = re.search(r'"scoped_memory_configs":\[([^\]]*)\]',
                      stage2).group(1)
    assert all(int(s) <= STEP_VMEM
               for s in re.findall(r'"size":"(\d+)"', asked)), asked
    used = int(re.search(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
        stage2).group(1))
    assert used <= STEP_VMEM, used


# each pallas_call's name -> a call of that kernel alone, at small widths
_NAMED = {
    "sketch_a_omega": (lambda a: ops.sketch_matmul(a, seed=0, r=128),
                       [((1024, 1024), jnp.float32)]),
    "sketch_omega_t_b": (lambda b: ops.sketch_t_matmul(b, seed=0, r=128),
                         [((1024, 128), jnp.float32)]),
    "gen_omega": (lambda: ops.gen_omega(seed=0, n2=512, r=128), []),
    "sketch_block": (
        lambda a: local.sketch_block(a, 0, 128, backend="pallas",
                                     interpret=False),
        [((1024, 1024), jnp.float32)]),
    "sketch_t_block": (
        lambda b: local.sketch_t_block(b, 0, 128, backend="pallas",
                                       interpret=False),
        [((1024, 128), jnp.float32)]),
    "gemm_block": (
        lambda a, b: local.gemm_block(a, b, backend="pallas",
                                      interpret=False),
        [((256, 512), jnp.float32), ((512, 128), jnp.float32)]),
    "fold_rows_block": (
        lambda y, d, s: local.fold_rows_block(y, d, s, backend="pallas",
                                              interpret=False),
        [((512, 128), jnp.float32), ((64, 128), jnp.float32),
         ((), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(_NAMED))
def test_kernel_carries_its_name(one_chip, name):
    """The compiled program's one custom call is named after the kernel."""
    fn, args = _NAMED[name]
    shapes = [_shape(one_chip, s, dt) for s, dt in args]
    text = jax.jit(fn, out_shardings=one_chip).lower(*shapes).compile() \
        .as_text()
    lines = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    named = [ln for ln in lines
             if re.search(rf"%{name}(\.\d+)? = ", ln)]
    assert len(lines) == 1 and named == lines, (name, [ln[:80]
                                                       for ln in lines])
