"""Elastic scaling: a checkpoint written under one mesh restores under
a different device count (node-failure recovery path).  Subprocesses
own their device counts (process-global in jax)."""
import os
import subprocess
import sys

_SAVE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
from repro.compat import auto_axis_types, make_mesh
mesh = make_mesh((2, 2), ("data", "model"),
                 axis_types=auto_axis_types(2))
w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                   NamedSharding(mesh, P("data", "model")))
cm = CheckpointManager(sys.argv[1])
cm.save(7, {"w": w})
print("SAVED")
"""

_RESTORE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
from repro.compat import auto_axis_types, make_mesh
from repro.runtime.fault import elastic_remesh_plan
plan = elastic_remesh_plan(len(jax.devices()), model_parallel=2)
mesh = make_mesh((plan["data"], plan["model"]), ("data", "model"),
                 axis_types=auto_axis_types(2))
sh = {"w": NamedSharding(mesh, P("data", "model"))}
cm = CheckpointManager(sys.argv[1])
like = {"w": jnp.zeros((8, 8))}
restored, step = cm.restore(like, shardings=sh)
assert step == 7
np.testing.assert_array_equal(np.asarray(restored["w"]),
                              np.arange(64.0).reshape(8, 8))
assert restored["w"].sharding.mesh.shape["model"] == 2
print("RESTORED", plan)
"""


def test_remesh_plan_edge_cases():
    """Failure-path inputs: the plan must stay internally consistent
    for any survivor count the scheduler can hand it."""
    import pytest

    from repro.runtime.fault import elastic_remesh_plan

    for n in (0, 1, 2, 3, 5, 6, 7, 12, 15, 16, 17, 100):
        plan = elastic_remesh_plan(n, model_parallel=16)
        assert plan["devices_used"] + plan["devices_idle"] == n, (n, plan)
        assert plan["grad_accum_factor"] >= 1, (n, plan)
        assert plan["devices_used"] == plan["data"] * plan["model"]

    # n_devices below model_parallel degrades to a power of two
    assert elastic_remesh_plan(6, model_parallel=16)["model"] == 4
    assert elastic_remesh_plan(1, model_parallel=16) == {
        "data": 1, "model": 1, "devices_used": 1, "devices_idle": 0,
        "grad_accum_factor": 16}
    # total outage: a degenerate-but-consistent plan, not a crash
    z = elastic_remesh_plan(0)
    assert z["devices_used"] == 0 and z["devices_idle"] == 0
    # an unsatisfiable data-parallel floor is an explicit error, never
    # a plan that oversubscribes the survivors
    with pytest.raises(ValueError):
        elastic_remesh_plan(4, model_parallel=4, min_data=2)


def test_straggler_monitor_unpaired_step_end():
    """step_end() without a prior step_start() is a no-op, not a
    TypeError (restart paths call step_end defensively)."""
    from repro.runtime.fault import StragglerMonitor

    mon = StragglerMonitor()
    assert mon.step_end(0) is False
    assert mon.mean_step_s is None and mon.events == []
    # a normal pair afterwards still records
    mon.step_start()
    assert mon.step_end(1) is False
    assert mon.mean_step_s is not None
    # step_end consumed the start: calling again is again a no-op
    before = mon.mean_step_s
    assert mon.step_end(2) is False
    assert mon.mean_step_s == before


def test_checkpoint_survives_remesh(tmp_path):
    d = str(tmp_path)
    r1 = subprocess.run([sys.executable, "-c", _SAVE, d], cwd=".",
                        capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run([sys.executable, "-c", _RESTORE, d], cwd=".",
                        capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "RESTORED" in r2.stdout
