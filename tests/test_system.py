"""End-to-end behaviour tests for the paper's system."""
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dist_helper import SRC


def test_end_to_end_sketch_to_nystrom_single_device():
    """Paper pipeline on one device: sketch -> core -> reconstruction,
    with the distributed-identical Philox Omega."""
    from repro.core import (nystrom_reference, relative_error,
                            sketch_reference)
    n, k, r = 128, 8, 32
    X = jax.random.normal(jax.random.key(0), (n, k))
    S = X @ X.T
    B = sketch_reference(S, 3, r)
    assert B.shape == (n, r)
    Bn, C = nystrom_reference(S, 3, r)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Bn), rtol=1e-5)
    assert float(relative_error(S, Bn, C)) < 1e-4


def _tab2_kernel(name, n=128, d=24):
    """Paper Tab. 2's kernels of n Gaussian points in d dimensions: linear
    (rank d), RBF at sigma = ||X||/sqrt(n), and RBF at sigma = 1."""
    X = jax.random.normal(jax.random.key(0), (n, d))
    if name == "linear":
        return X @ X.T
    sq = jnp.sum(X * X, 1)
    d2 = sq[:, None] + sq[None, :] - 2 * X @ X.T
    sigma = (float(jnp.linalg.norm(X)) / n ** 0.5 if name == "rbf_scaled"
             else 1.0)
    return jnp.exp(-d2 / (2 * sigma ** 2))


@pytest.mark.parametrize("name", ["linear", "rbf_scaled", "rbf_sigma1"])
def test_nystrom_error_follows_tab2(name):
    """Tab. 2's qualitative pattern at n = 128, d = 24: the linear kernel
    is recovered to about f32 precision once r > d, the well-scaled RBF's
    error falls with r, and the sigma = 1 RBF (nearly diagonal) stays
    O(1)."""
    from repro.core import nystrom_reference, relative_error
    A = _tab2_kernel(name)
    err = [float(relative_error(A, *nystrom_reference(A, 11, r)))
           for r in (8, 16, 32)]
    if name == "linear":
        assert err[1] > 0.1 and err[2] < 1e-4, err
    elif name == "rbf_scaled":
        assert err[0] > err[1] > err[2] and err[2] < 0.2, err
    else:
        assert min(err) > 0.5, err


def test_end_to_end_training_run():
    """Train a reduced LM for 60 steps: loss must drop, checkpoints must
    appear."""
    import tempfile
    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.data.pipeline import DataConfig
    from repro.models import get_api
    from repro.train.loop import train_loop
    from repro.train.step import init_state, make_train_step
    from repro.checkpoint import ckpt

    cfg = get_config("llama3-8b").reduced(n_layers=2, d_model=32, d_ff=64,
                                          vocab=64, head_dim=8)
    api = get_api(cfg)
    with tempfile.TemporaryDirectory() as d:
        run = RunConfig(steps=60, learning_rate=5e-3, warmup_steps=5,
                        checkpoint_every=20, checkpoint_dir=d, remat=False)
        data_cfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
        state = init_state(api, cfg, run, jax.random.key(0))
        step_fn = jax.jit(make_train_step(api, cfg, run))
        res = train_loop(step_fn, state, data_cfg, run)
        assert np.mean(res.losses[-10:]) < np.mean(res.losses[:10])
        assert ckpt.latest_step(d) == 60


def test_dryrun_single_cell_on_production_mesh():
    """The multi-pod dry-run machinery end-to-end for one cell on the real
    512-device mesh (subprocess; ~1 min)."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=512'\n"
        "from repro.launch.dryrun import run_cell\n"
        "rec = run_cell('whisper-tiny', 'train_4k', multi_pod=True)\n"
        "assert 'error' not in rec, rec\n"
        "assert rec['chips'] == 512\n"
        "assert rec['hlo_flops'] > 0 and rec['collective_bytes'] > 0\n"
        "print('OK', rec['bottleneck'])\n"
    )
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_serving_end_to_end():
    from repro.configs import get_config
    from repro.models import get_api
    from repro.serve.engine import BatchedServer, Request
    cfg = get_config("falcon-mamba-7b").reduced(n_layers=2)
    api = get_api(cfg)
    params = api.init(jax.random.key(0), cfg)
    server = BatchedServer(params, cfg, slots=2, max_len=32, eos=-1)
    reqs = [Request(rid=i, prompt=[1, 2 + i], max_new=4) for i in range(3)]
    for r in reqs:
        server.submit(r)
    server.run()
    assert all(r.done and len(r.out) == 4 for r in reqs)
