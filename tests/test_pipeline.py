"""Pipeline parallelism: GPipe schedule == serial execution (fwd + grads)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, %r)
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.pipeline import pipeline_apply, stage_params
    from repro.launch.mesh import make_mesh

    S, L, M, MB, D = 4, 8, 6, 2, 16
    rng = np.random.default_rng(0)
    Ws = jnp.asarray(rng.standard_normal((L, D, D)).astype(np.float32) * 0.2)
    x = jnp.asarray(rng.standard_normal((M, MB, D)).astype(np.float32))

    def layer(w, h):
        return jnp.tanh(h @ w)

    def stage_fn(w_slab, h):  # (L/S, D, D)
        for i in range(w_slab.shape[0]):
            h = layer(w_slab[i], h)
        return h

    def serial(Ws, x):
        h = x
        for i in range(L):
            h = layer(Ws[i], h)
        return h

    mesh = make_mesh((S,), ("stage",))
    staged = stage_params({"w": Ws}, S)["w"]
    y_pipe = pipeline_apply(stage_fn, staged, x, mesh)
    y_ser = jax.vmap(lambda xi: serial(Ws, xi))(x)
    fwd_err = float(jnp.abs(y_pipe - y_ser).max())
    assert fwd_err < 1e-5, f"fwd {fwd_err}"

    # grads through the pipeline == serial grads
    def loss_pipe(staged):
        return (pipeline_apply(stage_fn, staged, x, mesh) ** 2).sum()
    def loss_ser(Ws):
        return (jax.vmap(lambda xi: serial(Ws, xi))(x) ** 2).sum()
    g_pipe = jax.grad(loss_pipe)(staged).reshape(L, D, D)
    g_ser = jax.grad(loss_ser)(Ws)
    g_err = float(jnp.abs(g_pipe - g_ser).max() / (jnp.abs(g_ser).max() + 1e-9))
    assert g_err < 1e-4, f"grad {g_err}"
    print("OK", fwd_err, g_err)
""" % SRC)


def test_pipeline_matches_serial():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600)
    assert res.returncode == 0, f"stdout:{res.stdout}\nstderr:{res.stderr[-3000:]}"
    assert "OK" in res.stdout
