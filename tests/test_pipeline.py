"""GPipe pipeline-parallel engine: pipelined == sequential, forward and
backward. Needs >1 device → runs itself in a subprocess with 8 forced host
devices (the main pytest process keeps the real 1-device platform)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.distributed.pipeline import pipeline_apply, stack_for_stages
    from repro.launch.mesh import make_mesh

    S, LperS, mu, mb, d = 4, 2, 6, 3, 16
    L = S * LperS
    mesh = make_mesh((S, 2), ("stage", "model"))
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (L, d, d), jnp.float32) * (1.0 / d ** 0.5)
    x = jax.random.normal(jax.random.fold_in(key, 1), (mu, mb, d))

    def layer(w, h):
        return jnp.tanh(h @ w)

    # sequential reference
    def seq_apply(Ws, x):
        h = x
        for i in range(L):
            h = layer(Ws[i], h)
        return h
    ref = jax.vmap(seq_apply, in_axes=(None, 0))(Ws, x)

    def stage_fn(wslice, h):      # wslice: (L/S, d, d)
        def body(h, w):
            return layer(w, h), None
        h, _ = jax.lax.scan(body, h, wslice)
        return h

    staged = stack_for_stages(Ws, S)
    staged = jax.device_put(staged, NamedSharding(mesh, P("stage")))
    out = pipeline_apply(stage_fn, staged, x, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    print("FWD-OK")

    # backward: grads of a scalar loss w.r.t. stage params match sequential
    def loss_pipe(Ws_staged):
        return jnp.sum(pipeline_apply(stage_fn, Ws_staged, x, mesh=mesh) ** 2)

    def loss_seq(Ws_flat):
        return jnp.sum(jax.vmap(seq_apply, in_axes=(None, 0))(Ws_flat, x) ** 2)

    g_pipe = jax.grad(loss_pipe)(staged).reshape(L, d, d)
    g_seq = jax.grad(loss_seq)(Ws)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                               atol=1e-4, rtol=1e-4)
    print("BWD-OK")
""")


@pytest.mark.slow
def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "FWD-OK" in r.stdout and "BWD-OK" in r.stdout, (
        r.stdout[-2000:], r.stderr[-4000:])
