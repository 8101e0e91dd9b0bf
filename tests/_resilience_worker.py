"""Worker for the crash→restart-from-checkpoint test (not a pytest file).

Spawned in pairs by `tests/test_distributed.py::
test_worker_crash_restart_from_checkpoint`: 2 processes x 1 virtual CPU
device each, a real coordinator + gloo boundary between the blocks.  Three
modes driven by argv:

* ``normal`` — run NSTEPS diffusion steps with checkpointing, gather the
  final field to the root and save it (the uninterrupted reference).
* ``crash``  — same, but the parent armed ``IGG_FAULT_INJECT=
  worker_crash:step4:proc1``: process 1 hard-exits (status 17) right after
  the step-4 checkpoint completes; process 0 is reaped by the parent.
* ``resume`` — `RunGuard.start` restores the latest complete checkpoint
  (asserted to be step 4) and finishes the run; the final gather must be
  bit-identical to the ``normal`` output.

Watchdogged with `igg.watchdog` (the library generalization of the
hand-rolled `faulthandler` arming `_distributed_worker.py` used to carry):
a collective hang dumps all-thread stacks into the parent-captured log and
exits, instead of dying silently at the parent's outer timeout.
"""

import faulthandler
import os
import sys

# Pre-import watchdog: covers a stall inside the jax import itself; the
# igg.watchdog below replaces this timer once the package is importable.
# Must stay below the parent's 240 s wait (test_distributed.py finish_pair)
# so a hang dumps stacks into the parent-captured log before the kill.
faulthandler.dump_traceback_later(200, exit=True)

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
port = sys.argv[3]
mode = sys.argv[4]
ckptdir = sys.argv[5]
out_path = sys.argv[6]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 1)
jax.config.update("jax_enable_x64", True)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

import numpy as np

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.models import diffusion3d
from implicitglobalgrid_tpu.utils import resilience

NX = 8
NSTEPS = 6
CKPT_EVERY = 2

# Below the parent's 240 s wait: a collective hang dumps stacks into the
# parent-shown log and exits, instead of being killed silently at 240 s.
with igg.watchdog(200, exit=True):
    igg.init_global_grid(
        NX,
        NX,
        NX,
        quiet=(pid != 0),
        init_distributed=True,
        distributed_kwargs=dict(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=nproc,
            process_id=pid,
        ),
    )
    assert igg.get_global_grid().dims == (2, 1, 1), igg.get_global_grid().dims

    if mode == "resume":
        latest = igg.latest_checkpoint(ckptdir)
        assert latest is not None and latest.endswith("step_00000004"), (
            f"expected the crash run to leave a complete step-4 checkpoint, "
            f"found {latest!r}"
        )

    state, params = diffusion3d.setup(NX, NX, NX, init_grid=False)
    step = diffusion3d.make_step(params)
    guard = resilience.RunGuard(
        checkpoint_every=CKPT_EVERY, checkpoint_dir=ckptdir, names=("T", "Cp")
    )
    state = resilience.guarded_time_loop(
        step, state, NSTEPS, guard=guard, sync_every_step=True
    )
    # crash mode never reaches this point on any process: proc 1 hard-exits
    # at step 4 and proc 0 is reaped by the parent when its next collective
    # loses the peer.
    assert mode in ("normal", "resume"), mode

    T = diffusion3d.temperature(state)
    got = igg.gather(T, root=0)
    if jax.process_index() == 0:
        assert got is not None and np.isfinite(got).all()
        np.save(out_path, got)

    igg.finalize_global_grid()
    assert not igg.grid_is_initialized()

print(f"WORKER {pid} OK", flush=True)
