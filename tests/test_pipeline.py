"""GPipe pipeline over the pod axis: schedule correctness on a real 2-stage
mesh (subprocess with fake devices)."""

import os
import subprocess
import sys
import textwrap

_PP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel import pipeline

    mesh = jax.make_mesh((2,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    L, D, M, B = 4, 16, 3, 5   # 4 layers -> 2 stages x 2 layers
    key = jax.random.PRNGKey(0)
    ws = 0.3 * jax.random.normal(key, (L, D, D), jnp.float32)

    def layer(w, x):
        return jnp.tanh(x @ w)

    def stage(params, x):           # params: (L/S, D, D)
        def body(c, w):
            return layer(w, c), None
        y, _ = jax.lax.scan(body, x, params)
        return y

    xs = jax.random.normal(jax.random.PRNGKey(1), (M, B, D), jnp.float32)

    # Reference: all layers sequentially per microbatch.
    def full(x):
        y, _ = jax.lax.scan(lambda c, w: (layer(w, c), None), x, ws)
        return y
    want = jax.vmap(full)(xs)

    stage_params = pipeline.stack_stages(ws, 2)
    with jax.set_mesh(mesh):
        sp = jax.device_put(stage_params, jax.NamedSharding(mesh, P("pod")))
        got = jax.jit(lambda p, x: pipeline.gpipe_forward(
            stage, p, x, mesh=mesh, axis="pod"))(sp, xs)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, err
    # ppermute (the inter-pod hop) must appear in the compiled program.
    hlo = jax.jit(lambda p, x: pipeline.gpipe_forward(
        stage, p, x, mesh=mesh, axis="pod")).lower(sp, xs).compile().as_text()
    assert "collective-permute" in hlo
    print("PP_OK", err)
""")


def test_gpipe_two_stage_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    r = subprocess.run(
        [sys.executable, "-c", _PP_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "PP_OK" in r.stdout


def test_stack_stages_shapes():
    import jax.numpy as jnp
    from repro.parallel import pipeline

    tree = {"w": jnp.zeros((8, 3, 3)), "b": jnp.zeros((8, 3))}
    st = pipeline.stack_stages(tree, 4)
    assert st["w"].shape == (4, 2, 3, 3)
    assert st["b"].shape == (4, 2, 3)
