"""Gradient-compression collective: accuracy vs lax.psum.

Needs >1 device, so it runs in a subprocess with forced host devices (the
main pytest process must keep the 1-device CPU view).
"""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.parallel.collectives import compressed_allreduce

    mesh = jax.make_mesh((8,), ("pod",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096), jnp.float32)

    @partial(shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
             check_vma=False)
    def compressed(xs):
        return compressed_allreduce(xs[0], "pod")[None]

    @partial(shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
             check_vma=False)
    def exact(xs):
        return jax.lax.psum(xs, "pod")

    out, ref = compressed(x), exact(x)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.02, rel
    # all shards must agree (it is an all-reduce)
    assert float(jnp.max(jnp.abs(out - out[:1]))) < 1e-6
    print("OK", rel)
""")


@pytest.mark.slow
def test_compressed_allreduce_subprocess():
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"}
    # keep the parent's backend pin: without it jax probes for accelerator
    # plugins, which hangs on sandboxed hosts
    if "JAX_PLATFORMS" in os.environ:
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    result = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=300, env=env)
    assert result.returncode == 0, result.stderr[-2000:]
    assert "OK" in result.stdout
