"""2-process `jax.distributed` test — the reference's multi-rank coverage.

The reference runs its whole suite under real MPI with any rank count
(`/root/reference/test/runtests.jl:8-31`); the equivalent here is spawning
two coordinator-connected JAX processes on localhost (CPU backend, 4 virtual
devices each) and checking the distributed result against a single-process
run of the *same global problem* on this process's 8-device mesh.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax

NX = 8
NSTEPS = 3

_here = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pair_env():
    """Clean slate for spawned worker pairs: repo importable, no conftest
    side effects (workers configure jax themselves, before first device
    use), and no leaked fault spec from an outer harness."""
    env = dict(os.environ)
    env.pop("IGG_FAULT_INJECT", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.dirname(_here), env.get("PYTHONPATH")) if p
    )
    return env


@pytest.fixture(scope="module")
def dist_out_path(tmp_path_factory):
    port = _free_port()
    out = str(tmp_path_factory.mktemp("dist") / "gathered.npy")
    env = _pair_env()
    worker = os.path.join(_here, "_distributed_worker.py")
    logdir = tmp_path_factory.mktemp("dist_logs")
    logs = [open(logdir / f"worker{pid}.log", "w+") for pid in range(2)]
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), "2", str(port), out],
            env=env,
            stdout=logs[pid],
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    try:
        for pid, p in enumerate(procs):
            p.wait(timeout=480)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    finally:
        for f in logs:
            f.flush()
    outs = []
    for pid, (p, f) in enumerate(zip(procs, logs)):
        f.seek(0)
        outs.append((pid, p.returncode, f.read()))
        f.close()
    for pid, rc, stdout in outs:
        assert rc == 0, f"worker {pid} failed (rc={rc}):\n{stdout}"
        assert f"WORKER {pid} OK" in stdout
    return out


@pytest.fixture(scope="module")
def dist4_out_path(tmp_path_factory):
    """FOUR coordinator-connected processes, one virtual device each, on a
    2x2x1 mesh: two SIMULTANEOUS process boundaries (x and y) through the
    grid — the worker runs the compact scenario (fused-cadence exchange
    with corner carry-over, fill-in-place gather, coalesced-vs-per-field
    bit identity; ISSUE 5 satellite).  Shapes stay tiny (local 8^3, 4
    steps) so the tier-1 budget holds."""
    nproc = 4
    port = _free_port()
    out = str(tmp_path_factory.mktemp("dist4") / "gathered.npy")
    env = _pair_env()
    worker = os.path.join(_here, "_distributed_worker.py")
    logdir = tmp_path_factory.mktemp("dist4_logs")
    logs = [open(logdir / f"worker{pid}.log", "w+") for pid in range(nproc)]
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), str(nproc), str(port), out,
             "2x2x1"],
            env=env,
            stdout=logs[pid],
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(nproc)
    ]
    try:
        for pid, p in enumerate(procs):
            p.wait(timeout=480)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    finally:
        for f in logs:
            f.flush()
    outs = []
    for pid, (p, f) in enumerate(zip(procs, logs)):
        f.seek(0)
        outs.append((pid, p.returncode, f.read()))
        f.close()
    for pid, rc, stdout in outs:
        assert rc == 0, f"worker {pid} failed (rc={rc}):\n{stdout}"
        assert f"WORKER {pid} OK" in stdout
    return out


def test_four_process_2x2_mesh_matches_single_process(dist4_out_path):
    """The 4-process 2x2 run's fused-cadence result (two real gloo process
    boundaries, corner carry-over through both) must reproduce the same
    global problem run single-process with the SAME (2,2,1) decomposition
    on this process's own devices."""
    import warnings

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    state, params = diffusion3d.setup(
        NX, NX, NX, dimx=2, dimy=2, dimz=1, devices=jax.devices()[:4],
        overlapx=4, overlapy=4, overlapz=4, quiet=True,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        stepc = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=2)
        state = jax.block_until_ready(stepc(*state))
    expected = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()

    got = np.load(dist4_out_path)
    assert got.shape == expected.shape
    assert got.dtype == expected.dtype
    np.testing.assert_allclose(got, expected, rtol=1e-13, atol=1e-13)


def test_two_process_matches_single_process(dist_out_path):
    """The 2-process distributed run must reproduce the single-process run."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    # Same global problem on this process's own 8-device mesh: local 8^3,
    # 8 blocks, dims (2,2,2) in both setups.
    state, params = diffusion3d.setup(NX, NX, NX, quiet=True)
    step = diffusion3d.make_step(params)
    for _ in range(NSTEPS):
        state = jax.block_until_ready(step(*state))
    expected = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()

    got = np.load(dist_out_path)
    assert got.shape == expected.shape
    assert got.dtype == expected.dtype
    np.testing.assert_allclose(got, expected, rtol=1e-13, atol=1e-13)


def test_two_process_fused_cadence_matches_single_process(dist_out_path):
    """The production fused cadence's COMMUNICATION across a REAL process
    boundary (VERDICT r4 #3): the worker ran `make_multi_step(fused_k=2)` on
    its f64 deep-halo grid — the documented fallback runs the XLA cadence at
    the kernel path's exact exchange schedule (one width-2 slab exchange per
    2 steps), with gloo hops inside every exchange.  The same problem with
    the same decomposition single-process must agree bitwise-tight.  (The
    Pallas kernel itself cannot cross a process boundary in interpret mode —
    the interpreter barriers all global devices on local threads; see the
    worker's comment — and its arithmetic equivalence to the XLA cadence is
    pinned single-process in test_models_diffusion.py.)"""
    import warnings

    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    state, params = diffusion3d.setup(
        NX, NX, NX, overlapx=4, overlapy=4, overlapz=4, quiet=True
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        stepc = diffusion3d.make_multi_step(params, 4, donate=False, fused_k=2)
        state = jax.block_until_ready(stepc(*state))
    expected = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()

    got = np.load(dist_out_path + ".fused.npy")
    assert got.shape == expected.shape
    np.testing.assert_allclose(got, expected, rtol=1e-13, atol=1e-13)


def test_two_process_hide_communication_matches_single_process(dist_out_path):
    """`hide_communication` (overlap-scheduled exchange) across the real
    process boundary, against the same 8-block problem single-process."""
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d

    state, params = diffusion3d.setup(NX, NX, NX, hide_comm=True, quiet=True)
    step = diffusion3d.make_step(params, donate=False)
    for _ in range(NSTEPS):
        state = jax.block_until_ready(step(*state))
    expected = np.asarray(igg.gather(diffusion3d.temperature(state)))
    igg.finalize_global_grid()

    got = np.load(dist_out_path + ".hc.npy")
    assert got.shape == expected.shape
    np.testing.assert_allclose(got, expected, rtol=1e-13, atol=1e-13)


@pytest.mark.fault
def test_worker_crash_restart_from_checkpoint(tmp_path):
    """Kill one worker mid-run; restart the pair from the last checkpoint.

    The acceptance path of the resilience subsystem end to end, across a
    REAL process boundary: (1) an uninterrupted 2-process run is the
    reference; (2) the same run with ``IGG_FAULT_INJECT=
    worker_crash:step4:proc1`` loses process 1 right after the step-4
    checkpoint completes (exit status 17; the orphaned process 0 is
    reaped); (3) a restarted pair resumes from the step-4 checkpoint and
    finishes.  The resumed run's gathered field must be BIT-identical to
    the uninterrupted one.
    """
    import shutil

    worker = os.path.join(_here, "_resilience_worker.py")
    env_base = _pair_env()

    def spawn_pair(mode, ckptdir, out, extra_env=None):
        env = dict(env_base)
        env.update(extra_env or {})
        port = _free_port()
        logdir = tmp_path / f"logs_{mode}"
        logdir.mkdir(exist_ok=True)
        logs = [open(logdir / f"worker{pid}.log", "w+") for pid in range(2)]
        procs = [
            subprocess.Popen(
                [
                    sys.executable, worker, str(pid), "2", str(port),
                    mode, str(ckptdir), str(out),
                ],
                env=env,
                stdout=logs[pid],
                stderr=subprocess.STDOUT,
                text=True,
            )
            for pid in range(2)
        ]
        return procs, logs

    def read_logs(procs, logs):
        outs = []
        for p, f in zip(procs, logs):
            f.flush()
            f.seek(0)
            outs.append((p.returncode, f.read()))
            f.close()
        return outs

    def finish_pair(procs, logs, what):
        try:
            for p in procs:
                p.wait(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs = read_logs(procs, logs)
        for pid, (rc, log) in enumerate(outs):
            assert rc == 0, f"{what} worker {pid} failed (rc={rc}):\n{log}"
            assert f"WORKER {pid} OK" in log
        return outs

    # (1) uninterrupted reference run
    expected_path = tmp_path / "expected.npy"
    procs, logs = spawn_pair("normal", tmp_path / "ckpt_ref", expected_path)
    finish_pair(procs, logs, "reference")
    expected = np.load(expected_path)

    # (2) crash run: worker 1 hard-exits after the step-4 checkpoint
    crash_dir = tmp_path / "ckpt_crash"
    procs, logs = spawn_pair(
        "crash",
        crash_dir,
        tmp_path / "never.npy",
        extra_env={"IGG_FAULT_INJECT": "worker_crash:step4:proc1"},
    )
    try:
        procs[1].wait(timeout=240)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    # the survivor loses its peer mid-collective; reap it like an
    # orchestrator would
    try:
        procs[0].wait(timeout=30)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        procs[0].wait()
    outs = read_logs(procs, logs)
    assert procs[1].returncode == 17, (
        f"worker 1 should have crashed with the injected status 17, got "
        f"{procs[1].returncode}:\n{outs[1][1]}"
    )
    assert "WORKER 1 OK" not in outs[1][1]
    # the crash left a COMPLETE step-4 checkpoint (meta.json written after
    # the all-process barrier, before the injected exit)
    from implicitglobalgrid_tpu.utils.checkpoint import latest_checkpoint

    latest = latest_checkpoint(crash_dir)
    assert latest is not None and latest.endswith("step_00000004"), latest

    # (3) restart the pair against the same checkpoint dir: resumes at the
    # checkpointed step and must finish bit-identical to the reference
    got_path = tmp_path / "resumed.npy"
    procs, logs = spawn_pair("resume", crash_dir, got_path)
    finish_pair(procs, logs, "resume")
    got = np.load(got_path)
    assert got.shape == expected.shape and got.dtype == expected.dtype
    np.testing.assert_array_equal(got, expected)
    shutil.rmtree(tmp_path / "ckpt_ref", ignore_errors=True)


@pytest.mark.fault
def test_elastic_restart_shrunk_topology(tmp_path):
    """Crash + damaged newest generation -> shrunk-topology restart.

    The elastic-restart acceptance path across a REAL process boundary
    (docs/robustness.md): a 2-process gloo pair (dims (2,1,1), local 8^3,
    nxyz_g (14,8,8)) runs with ``IGG_FAULT_INJECT=worker_crash:step4:proc1,
    ckpt_corrupt:step4`` — process 1 dies right after the step-4 checkpoint
    AND that newest generation is bit-flipped in place.  The restart runs on
    ONE process (1 device, local (14,8,8) — the same implicit global grid),
    where `latest_checkpoint` must fall back to the step-2 generation and
    `restore_checkpoint` must reshard the 2-process shards onto the shrunk
    topology.  The finished run must match a never-crashed single-grid
    oracle of the same global problem (decomposition invariance).
    """
    worker = os.path.join(_here, "_resilience_worker.py")
    env = _pair_env()
    env["IGG_FAULT_INJECT"] = "worker_crash:step4:proc1,ckpt_corrupt:step4"
    crash_dir = tmp_path / "ckpt_crash"
    port = _free_port()
    logdir = tmp_path / "logs_crash"
    logdir.mkdir()
    logs = [open(logdir / f"worker{pid}.log", "w+") for pid in range(2)]
    procs = [
        subprocess.Popen(
            [
                sys.executable, worker, str(pid), "2", str(port),
                "crash", str(crash_dir), str(tmp_path / "never.npy"),
            ],
            env=env,
            stdout=logs[pid],
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    try:
        procs[1].wait(timeout=240)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        raise
    # the survivor loses its peer mid-collective; reap it like a supervisor
    try:
        procs[0].wait(timeout=30)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        procs[0].wait()
    for f in logs:
        f.flush()
        f.seek(0)
    outs = [f.read() for f in logs]
    for f in logs:
        f.close()
    assert procs[1].returncode == 17, (
        f"worker 1 should have crashed with status 17, got "
        f"{procs[1].returncode}:\n{outs[1]}"
    )
    assert "IGG_FAULT_INJECT(ckpt_corrupt)" in outs[0], outs[0]

    from implicitglobalgrid_tpu.utils.checkpoint import latest_checkpoint

    # the newest published generation is step 4, but it is damaged: the
    # verified scan must fall back to step 2
    newest = latest_checkpoint(crash_dir, verify=False)
    assert newest is not None and newest.endswith("step_00000004"), newest
    latest = latest_checkpoint(crash_dir)
    assert latest is not None and latest.endswith("step_00000002"), latest

    # never-crashed oracle: the same global problem on ONE device
    import implicitglobalgrid_tpu as igg
    from implicitglobalgrid_tpu.models import diffusion3d
    from implicitglobalgrid_tpu.utils import resilience

    def single_grid_run(ckptdir):
        igg.init_global_grid(14, 8, 8, quiet=True, devices=jax.devices()[:1])
        assert igg.get_global_grid().nxyz_g == (14, 8, 8)
        state, params = diffusion3d.setup(14, 8, 8, init_grid=False)
        step = diffusion3d.make_step(params)
        guard = resilience.RunGuard(
            checkpoint_every=2 if ckptdir else None,
            checkpoint_dir=ckptdir,
            names=("T", "Cp"),
        )
        state = resilience.guarded_time_loop(
            step, state, 6, guard=guard, sync_every_step=True
        )
        T = np.asarray(jax.block_until_ready(state[0]))
        igg.finalize_global_grid()
        return T

    oracle = single_grid_run(None)
    # shrunk-topology restart: resumes at step 2 (elastic reshard of the
    # 2-process shards), finishes the remaining 4 steps on 1 process
    got = single_grid_run(str(crash_dir))
    assert got.shape == oracle.shape
    np.testing.assert_allclose(got, oracle, rtol=1e-13, atol=1e-13)


def test_gather_invalid_root_raises():
    import implicitglobalgrid_tpu as igg

    igg.init_global_grid(NX, NX, NX, quiet=True)
    T = igg.zeros((NX, NX, NX))
    with pytest.raises(ValueError, match="root"):
        igg.gather(T, root=jax.process_count())
    with pytest.raises(ValueError, match="root"):
        igg.gather(T, root=-1)
    igg.finalize_global_grid()


def test_two_process_rank_tagged_telemetry_events(dist_out_path):
    """The 2-process gloo leg of the observability acceptance
    (docs/observability.md): both ranks of the worker pair must have
    written their OWN JSONL event file into the shared telemetry
    directory, every line rank/pid/coords-tagged and schema-complete, with
    the two ranks disagreeing exactly where they must (rank, pid, coords)."""
    from implicitglobalgrid_tpu.utils.telemetry import read_events

    tdir = dist_out_path + ".telemetry"
    f0 = os.path.join(tdir, "events.jsonl")
    f1 = os.path.join(tdir, "events.p1.jsonl")
    assert os.path.isfile(f0), f"rank 0 wrote no event log under {tdir}"
    assert os.path.isfile(f1), f"rank 1 wrote no event log under {tdir}"
    e0, e1 = read_events(f0), read_events(f1)
    checks = []
    for rank, events in ((0, e0), (1, e1)):
        for e in events:
            assert {"ts", "type", "rank", "pid", "coords"} <= set(e), e
        mine = [e for e in events if e["type"] == "worker.check"]
        assert len(mine) == 1, (rank, [e["type"] for e in events])
        assert mine[0]["rank"] == rank
        checks.append(mine[0])
    # Distinct processes, distinct blocks: pid and grid coords must differ.
    assert checks[0]["pid"] != checks[1]["pid"]
    assert checks[0]["coords"] != checks[1]["coords"]
    assert checks[0]["coords"] is not None and checks[1]["coords"] is not None


def test_two_process_merged_trace(dist_out_path):
    """ISSUE 10 acceptance: the real 2-process gloo run yields ONE merged
    Chrome trace — both ranks' ``igg.step`` and halo-exchange spans on the
    shared barrier-aligned clock, loadable as valid JSON, with per-track
    monotonic timestamps and the alignment honesty bound recorded."""
    import glob
    import json

    from implicitglobalgrid_tpu.utils import tracing

    tdir = dist_out_path + ".telemetry"
    files = sorted(glob.glob(os.path.join(tdir, "trace.p*.json")))
    assert len(files) == 2, f"expected both ranks' span files, got {files}"
    merged = tracing.merge_trace_files(files)
    # Valid Chrome-trace JSON: serializable, re-loadable, and clean under
    # the validator (which includes per-track ts monotonicity).
    doc = json.loads(json.dumps(merged))
    assert tracing.validate_chrome_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    for rank in (0, 1):
        names = {e["name"] for e in spans if e["pid"] == rank}
        assert "igg.step" in names, (rank, sorted(names))
        assert "igg_halo_exchange" in names, (rank, sorted(names))
        # the step spans carry their model/step tags into the args field
        steps = [
            e["args"]["step"] for e in spans
            if e["pid"] == rank and e["name"] == "igg.step"
        ]
        assert steps == sorted(steps) and len(steps) >= 4, steps
    align = doc["otherData"]["clock_alignment"]
    assert align["anchor_rank"] == 0
    for rank in ("0", "1"):
        per = align["per_rank"][rank]
        assert per["barrier_aligned"] is True
        assert isinstance(per["uncertainty_s"], (int, float))
        assert per["uncertainty_s"] >= 0


def test_two_process_device_merged_trace(dist_out_path):
    """ISSUE 15 acceptance, the real-boundary leg: both ranks of the gloo
    pair armed ``IGG_PROFILE=steps:2-3`` around the instrumented loop, so
    the run dir holds one capture meta + device trace per rank — and
    ``igg_trace.py merge --device`` must join BOTH ranks' device tracks
    into the ONE barrier-aligned Chrome trace, still valid, each rank's
    device ops on its own pid with the anchor honesty bound recorded."""
    import glob
    import json

    from implicitglobalgrid_tpu.utils import profiling, tracing

    tdir = dist_out_path + ".telemetry"
    metas = profiling.find_capture_metas(tdir)
    assert len(metas) == 2, f"expected both ranks' capture metas, got {metas}"
    files = sorted(glob.glob(os.path.join(tdir, "trace.p*.json")))
    doc = tracing.merge_trace_files(files)
    profiling.attach_device_tracks(doc, metas)
    doc = json.loads(json.dumps(doc))  # serializable + re-loadable
    assert tracing.validate_chrome_trace(doc) == []
    device = [
        e for e in doc["traceEvents"]
        if e.get("ph") == "X" and (e.get("args") or {}).get("hlo_op")
    ]
    assert {e["pid"] for e in device} == {0, 1}, (
        "both ranks' device tracks must be present"
    )
    for rank in (0, 1):
        ops = [e for e in device if e["pid"] == rank]
        assert all(
            e["tid"] >= profiling.DEVICE_TID_BASE for e in ops
        ), "device ops must ride dedicated device tids"
        assert all(e["args"].get("igg_scope") for e in ops)
    dev_align = doc["otherData"]["device_alignment"]
    assert set(dev_align["per_rank"]) == {"0", "1"}
    for rank in ("0", "1"):
        assert dev_align["per_rank"][rank]["n_ops"] > 0
