"""Elastic checkpoint/restore: save on one mesh, restore onto another.

Runs in a subprocess with 8 forced host devices (pytest's process keeps
seeing 1). The checkpoint format stores global arrays + manifest, so a
(4,2) training mesh restores onto a (2,4) mesh or a single device — the
device-count-independent restart path used for elastic scaling.
"""

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os, tempfile, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import save, restore, latest_step

    d = tempfile.mkdtemp()
    from jax.sharding import AxisType
    auto = (AxisType.Auto, AxisType.Auto)
    mesh_a = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)
    mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=auto)

    tree = {
        "w": jax.device_put(
            jnp.arange(64.0).reshape(8, 8),
            NamedSharding(mesh_a, P("data", "model"))),
        "b": jax.device_put(jnp.ones((8,)), NamedSharding(mesh_a, P("model"))),
        "step": jnp.int32(7),
    }
    save(d, 7, tree)
    assert latest_step(d) == 7

    # restore onto a DIFFERENT mesh layout (elastic reshard)
    shardings = {
        "w": NamedSharding(mesh_b, P("model", "data")),
        "b": NamedSharding(mesh_b, P(None)),
        "step": NamedSharding(mesh_b, P()),
    }
    out = restore(d, tree, 7, shardings=shardings)
    ok1 = bool(jnp.all(out["w"] == tree["w"]))
    ok2 = out["w"].sharding.spec == P("model", "data")

    # restore with no mesh at all (single-device recovery)
    out2 = restore(d, tree, 7)
    ok3 = bool(jnp.all(out2["w"] == tree["w"])) and int(out2["step"]) == 7
    print(json.dumps({"ok": ok1 and ok2 and ok3}))
""")


def test_elastic_reshard_roundtrip():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"]
