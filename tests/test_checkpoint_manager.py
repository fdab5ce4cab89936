"""Checkpoint subsystem: atomic commits, crash/resume, retention,
corruption handling, async overlap, preemption, and the trainer/IO
satellite fixes (ISSUE 5; docs/checkpointing.md).

The crash tests follow tests/test_dist_multiprocess.py's subprocess
pattern: tests/ckpt_worker.py runs a deterministic step-indexed training
loop, the parent SIGKILLs it mid-write, and a resumed process must match
the uninterrupted baseline bitwise.
"""
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _checkpoint_io, autograd, engine, gluon
from mxnet_tpu.checkpoint import (CheckpointCorrupt, CheckpointManager,
                                  CheckpointNotFound, verify_checkpoint)
from mxnet_tpu.checkpoint import manager as mgr_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "ckpt_worker.py")
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO, XLA_FLAGS="")

BATCH, FEATS = 8, 6


def _build(seed=7, optimizer="adam"):
    mx.random.seed(seed)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    net.add(gluon.nn.Dense(1))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            {"learning_rate": 1e-2})
    return net, trainer


def _train_one(net, trainer, step):
    rs = onp.random.RandomState(1000 + step)
    x = mx.np.array(rs.standard_normal((BATCH, FEATS)).astype("float32"))
    y = mx.np.array(rs.standard_normal((BATCH, 1)).astype("float32"))
    with autograd.record():
        loss = gluon.loss.L2Loss()(net(x), y)
    loss.backward()
    trainer.step(BATCH)
    return onp.float32(loss.asnumpy().sum())


def _params_of(trainer):
    return [p.data().asnumpy().copy() for p in trainer._params]


# -- roundtrip ---------------------------------------------------------------

def test_save_restore_bitwise_roundtrip(tmp_path):
    """Params, optimizer state trees, update counts, RNG key, scale and
    user_state all survive save->perturb->restore bit-for-bit."""
    net, trainer = _build()
    for s in range(1, 4):
        _train_one(net, trainer, s)
    mgr = CheckpointManager(tmp_path, trainer, keep_last=3)
    step = mgr.save(step=3, user_state={"epoch": 2, "cursor": [1, 2]})
    mgr.flush()
    assert step == 3 and mgr.latest_step() == 3

    want_params = _params_of(trainer)
    want_states = [tuple(x.asnumpy().copy() for x in s)
                   for s in trainer._states]
    want_counts = dict(trainer._optimizer._index_update_count)
    want_num_update = trainer._optimizer.num_update
    want_key = onp.asarray(mx._random._rng.key).copy()

    # wreck everything restorable
    for p in trainer._params:
        p.set_data(onp.zeros(p.shape, "float32"))
    trainer._states = [None] * len(trainer._params)
    trainer._states_created = [False] * len(trainer._params)
    trainer._optimizer.num_update = 0
    trainer._optimizer._index_update_count = {}
    mx.random.seed(999)

    res = mgr.restore()
    assert res.step == 3
    assert res.user_state == {"epoch": 2, "cursor": [1, 2]}
    for got, want in zip(_params_of(trainer), want_params):
        onp.testing.assert_array_equal(got, want)
    for got_s, want_s in zip(trainer._states, want_states):
        for got, want in zip(got_s, want_s):
            onp.testing.assert_array_equal(got.asnumpy(), want)
    assert trainer._optimizer._index_update_count == want_counts
    assert trainer._optimizer.num_update == want_num_update
    onp.testing.assert_array_equal(
        onp.asarray(mx._random._rng.key), want_key)
    # and training actually continues: one more step both ways agrees
    assert all(trainer._states_created)


def test_restore_ignores_meta_key_of_deleted_layout_pass(tmp_path):
    """Every checkpoint written before the layout pass was deleted
    carries ``meta["layout_perms"]`` — one None per parameter, since the
    pass was off by default — beside arrays in the logical layout. The
    key is not read: such a checkpoint restores parameters and
    optimizer state bit for bit."""
    import json

    net, trainer = _build()
    for s in range(1, 3):
        _train_one(net, trainer, s)
    mgr = CheckpointManager(tmp_path, trainer)
    mgr.save(step=2)
    mgr.flush()
    mpath = os.path.join(str(tmp_path), mgr_mod._STEP_FMT.format(2),
                         mgr_mod.MANIFEST_NAME)
    with open(mpath) as f:
        manifest = json.load(f)
    assert "layout_perms" not in manifest["meta"]
    manifest["meta"]["layout_perms"] = [None] * len(trainer._params)
    with open(mpath, "w") as f:
        json.dump(manifest, f)

    want_params = _params_of(trainer)
    want_states = [tuple(x.asnumpy().copy() for x in s)
                   for s in trainer._states]
    for p in trainer._params:
        p.set_data(onp.zeros(p.shape, "float32"))
    trainer._states = [None] * len(trainer._params)

    assert mgr.restore().step == 2
    for got, want in zip(_params_of(trainer), want_params):
        onp.testing.assert_array_equal(got, want)
    for got_s, want_s in zip(trainer._states, want_states):
        for got, want in zip(got_s, want_s):
            onp.testing.assert_array_equal(got.asnumpy(), want)


def test_resume_matches_uninterrupted_in_process(tmp_path):
    """Save at step 4, keep training to 10; a restored trainer re-running
    5..10 must reproduce the SAME losses bitwise (CPU XLA is
    deterministic; any state the checkpoint dropped would diverge)."""
    net, trainer = _build()
    mgr = CheckpointManager(tmp_path, trainer, keep_last=2)
    for s in range(1, 5):
        _train_one(net, trainer, s)
    mgr.save(step=4)
    mgr.flush()
    want = [_train_one(net, trainer, s) for s in range(5, 11)]

    mgr.restore()
    got = [_train_one(net, trainer, s) for s in range(5, 11)]
    onp.testing.assert_array_equal(onp.asarray(got), onp.asarray(want))


def test_sharded_mode_single_worker_roundtrip(tmp_path):
    """mode='sharded' with world=1: shard-00000.npz payload, same atomic
    manifest protocol, restore + verify both pass."""
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer, mode="sharded")
    mgr.save(step=1)
    mgr.flush()
    assert os.path.isfile(
        os.path.join(mgr.step_dir(1), "shard-00000.npz"))
    want = _params_of(trainer)
    for p in trainer._params:
        p.set_data(onp.zeros(p.shape, "float32"))
    assert mgr.restore().step == 1
    for got, w in zip(_params_of(trainer), want):
        onp.testing.assert_array_equal(got, w)
    assert verify_checkpoint(str(tmp_path))["ok"]


# -- discovery / retention / corruption --------------------------------------

def test_restore_empty_dir_raises_not_found(tmp_path):
    _, trainer = _build()
    mgr = CheckpointManager(tmp_path / "empty", trainer)
    with pytest.raises(CheckpointNotFound):
        mgr.restore()
    assert mgr.latest_step() is None


def test_retention_keep_last_and_milestones(tmp_path):
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer, keep_last=2,
                            keep_every_n_steps=4)
    for s in range(1, 7):
        mgr.save(step=s, sync=True)
    # keep_last=2 -> {5,6}; step 4 is a milestone (4 % 4 == 0) kept
    assert mgr.steps() == [4, 5, 6]


def test_corrupt_explicit_step_raises(tmp_path):
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer)
    mgr.save(step=1, sync=True)
    npz = os.path.join(mgr.step_dir(1), "arrays.npz")
    with open(npz, "r+b") as f:
        # corrupt a 256-byte stretch so the damage can't hide inside
        # zip alignment padding
        f.seek(os.path.getsize(npz) // 2)
        chunk = bytearray(f.read(256))
        f.seek(-len(chunk), os.SEEK_CUR)
        f.write(bytes(b ^ 0xFF for b in chunk))
    with pytest.raises(CheckpointCorrupt):
        mgr.restore(step=1)
    assert not verify_checkpoint(str(tmp_path), step=1)["ok"]


def test_corrupt_latest_falls_back_to_previous_good(tmp_path):
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer)
    mgr.save(step=1, sync=True)
    good = _params_of(trainer)
    _train_one(net, trainer, 2)
    mgr.save(step=2, sync=True)
    # truncate the latest payload: crc/shape checks must reject it
    npz = os.path.join(mgr.step_dir(2), "arrays.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 2)
    with pytest.warns(UserWarning, match="corrupt"):
        res = mgr.restore()
    assert res.step == 1
    for got, w in zip(_params_of(trainer), good):
        onp.testing.assert_array_equal(got, w)


def test_partial_tmp_ignored_and_reaped(tmp_path):
    """An uncommitted .tmp-* dir (crash mid-write) is invisible to
    steps()/restore() and reaped by the next manager init; a step dir
    missing its manifest is likewise not 'committed'."""
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer)
    mgr.save(step=1, sync=True)
    stale = tmp_path / ".tmp-step-00000009"
    stale.mkdir()
    (stale / "arrays.npz").write_bytes(b"partial garbage")
    orphan = tmp_path / "step-00000008"
    orphan.mkdir()
    (orphan / "arrays.npz").write_bytes(b"no manifest")
    assert mgr.steps() == [1]
    assert mgr.restore().step == 1
    CheckpointManager(tmp_path, trainer)  # init reaps stale tmp
    assert not stale.exists()


# -- async overlap -----------------------------------------------------------

def test_async_save_overlaps_training(tmp_path):
    """save() must return after snapshot capture, not after the write:
    with the write wedged open on the IO thread, training steps keep
    completing and the checkpoint only commits once the write finishes
    (acceptance criterion: save doesn't block Trainer.step)."""
    if engine.native_engine() is None or engine.is_naive():
        pytest.skip("async path needs the native engine")
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer, async_save=True)
    started, release = threading.Event(), threading.Event()

    def wedge(path):  # noqa: ARG001 — runs on the engine IO thread
        started.set()
        release.wait(30)

    mgr_mod._WRITE_BEGIN_HOOK = wedge
    try:
        t0 = time.perf_counter()
        mgr.save(step=1)
        returned = time.perf_counter() - t0
        assert started.wait(10), "write op never started"
        # write is wedged open: the save must already have returned and
        # training must proceed while it hangs
        assert returned < 5.0
        for s in range(2, 5):
            _train_one(net, trainer, s)
        assert mgr.steps() == []  # nothing committed while wedged
    finally:
        release.set()
        mgr_mod._WRITE_BEGIN_HOOK = None
    mgr.flush()
    assert mgr.steps() == [1]
    assert verify_checkpoint(str(tmp_path), step=1)["ok"]


def test_async_resave_same_step_serializes_tmp_reset(tmp_path):
    """Re-saving a step while its previous async write is still in
    flight must not pull the tmp dir out from under the IO thread: the
    reset runs on the serialized chain, so write/commit pairs execute
    in order and the step still commits cleanly."""
    if engine.native_engine() is None or engine.is_naive():
        pytest.skip("async path needs the native engine")
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer, async_save=True)
    started, release = threading.Event(), threading.Event()
    calls = []

    def wedge(path):  # noqa: ARG001 — runs on the engine IO thread
        calls.append(path)
        if len(calls) == 1:
            started.set()
            release.wait(30)

    mgr_mod._WRITE_BEGIN_HOOK = wedge
    try:
        mgr.save(step=1)
        assert started.wait(10), "first write op never started"
        mgr.save(step=1)        # re-save while the first write is wedged
        release.set()
        mgr.flush()
    finally:
        mgr_mod._WRITE_BEGIN_HOOK = None
    assert len(calls) == 2      # both writes ran, in order
    assert mgr.steps() == [1]
    assert verify_checkpoint(str(tmp_path), step=1)["ok"]


# -- emulated multi-worker (threads + a real collective barrier) -------------

class _FakeKV:
    """Two-'worker' kvstore stand-in: a real threading.Barrier plays the
    collective, so a rank that skips (or adds) a fence deadlocks exactly
    like TPUDist.barrier() would — surfaced as BrokenBarrierError by the
    timeout instead of hanging the suite."""

    def __init__(self, rank, world, gate):
        self.rank = rank
        self.num_workers = world
        self._gate = gate
        self.barrier_calls = 0

    def barrier(self):
        self.barrier_calls += 1
        self._gate.wait(timeout=60)


def _run_ranks(fn, world=2):
    errs = []

    def body(rank):
        try:
            fn(rank)
        except Exception as e:  # noqa: BLE001 — reported via errs
            errs.append((rank, e))

    threads = [threading.Thread(target=body, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    return errs


def test_replicated_multiworker_barrier_counts_match(tmp_path):
    """Regression: in replicated mode every rank must execute the SAME
    fence sequence. Rank!=0 early-returning after one barrier used to
    deadlock rank 0 at its second (pre-commit) fence on every
    distributed save."""
    net, trainer = _build()
    _train_one(net, trainer, 1)
    gate = threading.Barrier(2)
    kvs = [_FakeKV(r, 2, gate) for r in range(2)]
    mgrs = [CheckpointManager(tmp_path, trainer, kvstore=kvs[r])
            for r in range(2)]

    errs = _run_ranks(lambda r: mgrs[r].save(step=1))
    assert not errs, errs
    assert kvs[0].barrier_calls == kvs[1].barrier_calls == 3
    assert verify_checkpoint(str(tmp_path), step=1)["ok"]
    # rank 1 is a pure no-op writer: one payload + one manifest, nothing else
    assert sorted(os.listdir(mgrs[0].step_dir(1))) == \
        ["MANIFEST.json", "arrays.npz"]


def test_sharded_multiworker_fragments_merge_before_commit(tmp_path):
    """Regression: rank 0's manifest merge must only run once every
    rank's fragment manifest is durably on disk (fragments are written
    by write_op, before the pre-commit fence — not inside commit where
    the merge could race them)."""
    net, trainer = _build()
    _train_one(net, trainer, 1)
    gate = threading.Barrier(2)
    kvs = [_FakeKV(r, 2, gate) for r in range(2)]
    mgrs = [CheckpointManager(tmp_path, trainer, mode="sharded",
                              kvstore=kvs[r]) for r in range(2)]

    errs = _run_ranks(lambda r: mgrs[r].save(step=1))
    assert not errs, errs
    assert kvs[0].barrier_calls == kvs[1].barrier_calls == 3
    rep = verify_checkpoint(str(tmp_path), step=1)
    assert rep["ok"], rep
    d = mgrs[0].step_dir(1)
    assert os.path.isfile(os.path.join(d, "shard-00000.npz"))
    assert os.path.isfile(os.path.join(d, "shard-00001.npz"))
    # the merged manifest covers BOTH ranks' shares: a fresh single-worker
    # manager restores the full state from it
    want = _params_of(trainer)
    for p in trainer._params:
        p.set_data(onp.zeros(p.shape, "float32"))
    assert CheckpointManager(tmp_path, trainer).restore(step=1).step == 1
    for got, w in zip(_params_of(trainer), want):
        onp.testing.assert_array_equal(got, w)


# -- kill -9 mid-write (subprocess) ------------------------------------------

@pytest.fixture(scope="module")
def baseline_run(tmp_path_factory):
    outdir = tmp_path_factory.mktemp("baseline")
    out = subprocess.run([sys.executable, WORKER, "baseline", str(outdir)],
                         env=ENV, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(onp.load(os.path.join(outdir, "baseline.npz")))


def test_sigkill_mid_write_then_bitwise_resume(tmp_path, baseline_run):
    """The acceptance criterion end-to-end: a worker commits step 4,
    trains on, starts an async save and is SIGKILLed while the payload
    write is open. A fresh process must restore step 4 (checksum-
    verified, the partial write invisible) and its steps 5..10 must be
    BITWISE-identical — losses and final params — to the uninterrupted
    baseline."""
    outdir, ckdir = tmp_path / "out", tmp_path / "ck"
    outdir.mkdir()
    proc = subprocess.Popen(
        [sys.executable, WORKER, "kill", str(outdir), str(ckdir)],
        env=ENV, stdout=subprocess.DEVNULL,
        stderr=open(outdir / "stderr", "wb"))
    marker = outdir / "write_started"
    deadline = time.time() + 120
    while not marker.exists():
        assert proc.poll() is None, \
            (outdir / "stderr").read_bytes()[-2000:]
        assert time.time() < deadline, "worker never started the write"
        time.sleep(0.02)
    proc.kill()                     # SIGKILL mid-write
    proc.wait(timeout=60)
    assert proc.returncode == -signal.SIGKILL

    # the committed step-4 checkpoint must verify; step 6 must not exist
    assert verify_checkpoint(str(ckdir), step=4)["ok"]
    assert not os.path.isdir(os.path.join(str(ckdir), "step-00000006"))

    out = subprocess.run(
        [sys.executable, WORKER, "resume", str(outdir), str(ckdir)],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    resumed = dict(onp.load(outdir / "resume.npz"))

    for s in range(5, 11):          # 6 post-restore steps, bitwise
        onp.testing.assert_array_equal(
            resumed[f"loss/{s}"], baseline_run[f"loss/{s}"],
            err_msg=f"loss at step {s} diverged after resume")
    for k in baseline_run:
        if k.startswith("param/"):
            onp.testing.assert_array_equal(
                resumed[k], baseline_run[k],
                err_msg=f"final {k} diverged after resume")


def test_sigterm_preemption_snapshot_and_clean_exit(tmp_path):
    """SIGTERM -> emergency synchronous snapshot (reason='preempt') ->
    exit 0; the checkpoint restores in a fresh process."""
    outdir, ckdir = tmp_path / "out", tmp_path / "ck"
    outdir.mkdir()
    proc = subprocess.Popen(
        [sys.executable, WORKER, "preempt", str(outdir), str(ckdir)],
        env=ENV, stdout=subprocess.DEVNULL,
        stderr=open(outdir / "stderr", "wb"))
    ready = outdir / "ready"
    deadline = time.time() + 120
    while not ready.exists():
        assert proc.poll() is None, \
            (outdir / "stderr").read_bytes()[-2000:]
        assert time.time() < deadline, "worker never armed the handler"
        time.sleep(0.02)
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=120)
    assert proc.returncode == 0, \
        (outdir / "stderr").read_bytes()[-2000:]

    rep = verify_checkpoint(str(ckdir))
    assert rep["ok"], rep
    with open(os.path.join(str(ckdir), f"step-{rep['step']:08d}",
                           "MANIFEST.json"), encoding="utf-8") as f:
        import json

        manifest = json.load(f)
    assert manifest["reason"] == "preempt"
    assert manifest["meta"]["user_state"] == {"next_step": 5}

    _, trainer = _build()
    assert CheckpointManager(ckdir, trainer).restore().step == rep["step"]


def test_preemption_failed_snapshot_exits_nonzero(tmp_path):
    """A FAILED emergency snapshot must not exit with the configured
    'clean, resumable' code (default 0) — the supervisor would believe
    the latest state was saved when it was not. Expect exit 1 + a
    FAILED notice on stderr."""
    outdir, ckdir = tmp_path / "out", tmp_path / "ck"
    outdir.mkdir()
    proc = subprocess.Popen(
        [sys.executable, WORKER, "preempt_fail", str(outdir), str(ckdir)],
        env=ENV, stdout=subprocess.DEVNULL,
        stderr=open(outdir / "stderr", "wb"))
    ready = outdir / "ready"
    deadline = time.time() + 120
    while not ready.exists():
        assert proc.poll() is None, \
            (outdir / "stderr").read_bytes()[-2000:]
        assert time.time() < deadline, "worker never armed the handler"
        time.sleep(0.02)
    proc.send_signal(signal.SIGTERM)
    proc.wait(timeout=120)
    err = (outdir / "stderr").read_bytes()
    assert proc.returncode == 1, (proc.returncode, err[-2000:])
    assert b"FAILED" in err, err[-2000:]


# -- trainer save/load_states satellites -------------------------------------

def test_trainer_states_roundtrip_grad_versions_and_counts(tmp_path):
    """Format-2 save_states round-trips stale-grad tracking and the
    per-param update counts that Adam bias correction reads."""
    net, trainer = _build()
    for s in range(1, 3):
        _train_one(net, trainer, s)
    # grads are now STALE (updated, nothing new backprop'd)
    stale_before = trainer._stale_indices()
    assert stale_before  # every trained param is stale right after update
    counts = dict(trainer._optimizer._index_update_count)
    fname = str(tmp_path / "t.states")
    trainer.save_states(fname)

    net2, trainer2 = _build(seed=7)
    _train_one(net2, trainer2, 9)   # divergent state to be overwritten
    trainer2.load_states(fname)
    assert trainer2._stale_indices() == stale_before
    assert trainer2._optimizer._index_update_count == counts
    assert trainer2._optimizer.num_update == trainer._optimizer.num_update
    for s1, s2 in zip(trainer._states, trainer2._states):
        for a, b in zip(s1, s2):
            onp.testing.assert_array_equal(a.asnumpy(), b.asnumpy())


def test_trainer_load_states_count_mismatch_raises(tmp_path):
    net, trainer = _build()
    _train_one(net, trainer, 1)
    fname = str(tmp_path / "t.states")
    trainer.save_states(fname)

    mx.random.seed(1)
    other = gluon.nn.Dense(3)
    other.initialize()
    t2 = gluon.Trainer(other.collect_params(), "adam")
    with pytest.raises(ValueError, match="parameter"):
        t2.load_states(fname)


def test_trainer_load_states_dtype_mismatch_raises(tmp_path):
    net, trainer = _build()
    _train_one(net, trainer, 1)
    fname = str(tmp_path / "t.states")
    trainer.save_states(fname)

    mx.random.seed(7)
    net2 = gluon.nn.Sequential()
    net2.add(gluon.nn.Dense(16, activation="relu"))
    net2.add(gluon.nn.Dense(1))
    net2.initialize()
    params2 = net2.collect_params()
    for p in params2.values():
        p.dtype = "float16"
    t2 = gluon.Trainer(params2, "adam")
    with pytest.raises(ValueError, match="dtype"):
        t2.load_states(fname)


# -- _checkpoint_io satellites ------------------------------------------------

def test_wait_for_path_chains_original_traceback(tmp_path):
    """The write-fails-then-load regression: the exception surfaced at
    wait_for_path must be the ORIGINAL exception object — real type,
    original traceback frames from the IO thread — not a stringly
    reconstruction."""
    bad = str(tmp_path / "no_such_dir" / "x.npz")
    raised = None
    try:
        _checkpoint_io.async_save_npz(bad, {"a": onp.ones(3, "f")})
        _checkpoint_io.wait_for_path(bad)
    except Exception as e:
        raised = e
    assert isinstance(raised, FileNotFoundError)
    frames = traceback.extract_tb(raised.__traceback__)
    assert any(f.filename.endswith("_checkpoint_io.py") and
               f.name == "write" for f in frames), \
        f"original traceback lost: {[(f.filename, f.name) for f in frames]}"
    if engine.native_engine() is not None and not engine.is_naive():
        # the engine's stringly reconstruction rides along as context
        assert raised.__cause__ is not None or raised.__context__ is not None
    # the error was consumed: a later wait on the same path is clean
    _checkpoint_io.wait_for_path(bad)


def test_flush_all_barriers_and_raises_first_error(tmp_path):
    good = str(tmp_path / "ok.npz")
    bad = str(tmp_path / "missing_dir" / "bad.npz")
    _checkpoint_io.async_save_npz(good, {"a": onp.arange(4.0)})
    with pytest.raises(FileNotFoundError):
        _checkpoint_io.async_save_npz(bad, {"b": onp.arange(4.0)})
        _checkpoint_io.flush_all()
    # the good path landed despite the bad one failing
    _checkpoint_io.wait_for_path(good)
    assert onp.load(good)["a"].shape == (4,)


def test_manager_flush_surfaces_async_write_failure(tmp_path):
    """A failed async payload write must NOT commit, and flush() must
    re-raise the original error."""
    if engine.native_engine() is None or engine.is_naive():
        pytest.skip("async failure path needs the native engine")
    net, trainer = _build()
    _train_one(net, trainer, 1)
    mgr = CheckpointManager(tmp_path, trainer, async_save=True)

    def explode(path):  # noqa: ARG001
        raise OSError("disk on fire")

    mgr_mod._WRITE_BEGIN_HOOK = explode
    try:
        mgr.save(step=1)
        with pytest.raises(OSError, match="disk on fire"):
            mgr.flush()
    finally:
        mgr_mod._WRITE_BEGIN_HOOK = None
    assert mgr.steps() == []  # the commit op refused to run


# -- estimator handler --------------------------------------------------------

def test_estimator_checkpoint_handler_manager_mode(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator.event_handler import \
        CheckpointHandler

    net, trainer = _build()

    class Est:
        pass

    est = Est()
    est.net, est.trainer = net, trainer
    mgr = CheckpointManager(tmp_path / "ck", keep_last=3)
    h = CheckpointHandler(str(tmp_path / "legacy"), manager=mgr,
                          batch_period=2)
    for s in range(1, 5):
        _train_one(net, trainer, s)
        h.batch_end(est)
    mgr.flush()
    assert mgr.steps() == [2, 4]
    # legacy .params files are NOT written in manager mode
    assert not any(f.endswith(".params")
                   for f in os.listdir(tmp_path / "legacy"))

    net2, trainer2 = _build()
    est2 = Est()
    est2.net, est2.trainer = net2, trainer2
    h2 = CheckpointHandler(str(tmp_path / "legacy"),
                           manager=CheckpointManager(tmp_path / "ck"),
                           resume_from_checkpoint=True)
    h2.train_begin(est2)
    assert h2.current_batch == 4
    for got, want in zip(_params_of(trainer2), _params_of(trainer)):
        onp.testing.assert_array_equal(got, want)

    # cold directory: resume is a silent no-op, not an error
    h3 = CheckpointHandler(str(tmp_path / "legacy"),
                           manager=CheckpointManager(tmp_path / "cold"),
                           resume_from_checkpoint=True)
    h3.train_begin(est2)
    assert h3.current_batch == 0


# -- telemetry ----------------------------------------------------------------

def test_ckpt_telemetry_counters(tmp_path):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import instruments as ti

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        base_saves = ti.ckpt_save_total.labels("replicated", "ok").value
        base_restores = ti.ckpt_restore_total.labels("ok").value
        net, trainer = _build()
        _train_one(net, trainer, 1)
        mgr = CheckpointManager(tmp_path, trainer)
        mgr.save(step=1, sync=True)
        mgr.restore()
        assert ti.ckpt_save_total.labels("replicated", "ok").value == \
            base_saves + 1
        assert ti.ckpt_restore_total.labels("ok").value == base_restores + 1
    finally:
        if not was_enabled:
            telemetry.disable()


def test_ckpt_telemetry_error_outcome_on_failed_save(tmp_path):
    """A failed async payload write must be visible in metrics as
    ckpt_save_total{outcome="error"}, not silently absent."""
    if engine.native_engine() is None or engine.is_naive():
        pytest.skip("async failure path needs the native engine")
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import instruments as ti

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        base = ti.ckpt_save_total.labels("replicated", "error").value
        net, trainer = _build()
        _train_one(net, trainer, 1)
        mgr = CheckpointManager(tmp_path, trainer, async_save=True)

        def explode(path):  # noqa: ARG001
            raise OSError("disk on fire")

        mgr_mod._WRITE_BEGIN_HOOK = explode
        try:
            mgr.save(step=1)
            with pytest.raises(OSError, match="disk on fire"):
                mgr.flush()
        finally:
            mgr_mod._WRITE_BEGIN_HOOK = None
        assert ti.ckpt_save_total.labels("replicated", "error").value == \
            base + 1
    finally:
        if not was_enabled:
            telemetry.disable()
