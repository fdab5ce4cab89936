"""Tools: im2rec pack/read round-trip, launch.py local mode, bandwidth
(reference: tools/im2rec, tools/launch.py, tools/bandwidth/measure.py),
and the diagnose / ckpt / blackbox / fleetctl command lines.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)

PIL = pytest.importorskip("PIL")
from PIL import Image  # noqa: E402


@pytest.fixture()
def img_root(tmp_path):
    for cls in ("cat", "dog"):
        d = tmp_path / "imgs" / cls
        d.mkdir(parents=True)
        for i in range(3):
            arr = np.random.randint(0, 255, (32, 32, 3)).astype("uint8")
            Image.fromarray(arr).save(str(d / f"{cls}{i}.jpg"))
    return str(tmp_path / "imgs")


def test_im2rec_list_and_pack(img_root, tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import im2rec

    prefix = str(tmp_path / "data")
    lists = im2rec.make_list(prefix, img_root, shuffle=False)
    assert os.path.exists(lists[0])
    lines = open(lists[0]).read().strip().split("\n")
    assert len(lines) == 6
    labels = {line.split("\t")[1] for line in lines}
    assert labels == {"0", "1"}

    n = im2rec.pack_list(prefix, img_root)
    assert n == 6
    assert os.path.exists(prefix + ".rec")

    # read back through ImageRecordIter
    import mxnet_tpu as mx

    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 32, 32), batch_size=3)
    batch = next(it)
    assert batch.data[0].shape == (3, 3, 32, 32)
    assert batch.label[0].shape == (3,)


def test_im2rec_cli(img_root, tmp_path):
    prefix = str(tmp_path / "cli")
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         prefix, img_root, "--no-shuffle"],
        env=ENV, capture_output=True, text=True)
    assert rc.returncode == 0, rc.stderr
    assert os.path.exists(prefix + ".rec")


def test_launch_local_spawns_ranked_workers(tmp_path):
    marker = str(tmp_path / "rank")
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(
            "import os\n"
            f"open({marker!r} + os.environ['MXTPU_WORKER_RANK'], 'w')"
            ".write(os.environ['MXTPU_NUM_WORKERS'])\n")
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "3", sys.executable, script],
        env=ENV, capture_output=True, text=True)
    assert rc.returncode == 0, rc.stderr
    for r in range(3):
        assert open(marker + str(r)).read() == "3"


def test_bandwidth_harness():
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bandwidth.py"),
         "--sizes-mb", "0.25", "--iters", "2"],
        env=dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=300)
    assert rc.returncode == 0, rc.stderr
    row = json.loads(rc.stdout.strip().split("\n")[-1])
    assert row["n_devices"] == 4
    assert row["algo_bw_gbps"] > 0


def test_diagnose_passes_smoke():
    """tools/diagnose.py --passes: the graph-pass demo runs, the report
    gains the passes section, and --json carries the same content
    (docs/passes.md)."""
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py"),
         "--steps", "1", "--passes"],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert rc.returncode == 0, rc.stderr[-2000:]
    assert "== graph passes ==" in rc.stdout
    assert "pass amp: applied" in rc.stdout

    rj = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py"),
         "--steps", "1", "--passes", "--json"],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert rj.returncode == 0, rj.stderr[-2000:]
    report = json.loads(rj.stdout.strip().split("\n")[-1])
    pr = report["passes"]
    assert pr["pipeline_enabled"] is True
    assert pr["pass_applied"].get("amp", 0) >= 1


def test_ckpt_cli_verify_smoke(tmp_path):
    """tools/ckpt.py verify: exit 0 on a good checkpoint, 1 on a
    corrupted payload, 2 when nothing is committed — the pre-resume
    guard contract (docs/checkpointing.md)."""
    ckdir = str(tmp_path / "ck")
    seed = ("import mxnet_tpu as mx, numpy as onp\n"
            "from mxnet_tpu import autograd, gluon\n"
            "net = gluon.nn.Dense(4); net.initialize()\n"
            "tr = gluon.Trainer(net.collect_params(), 'sgd',\n"
            "                   {'learning_rate': 0.1, 'momentum': 0.9})\n"
            "x = mx.np.array(onp.ones((2, 3), 'float32'))\n"
            "with autograd.record():\n"
            "    loss = gluon.loss.L2Loss()(net(x), mx.np.zeros((2, 4)))\n"
            "loss.backward(); tr.step(2)\n"
            f"mgr = mx.checkpoint.CheckpointManager({ckdir!r}, tr)\n"
            "mgr.save(step=7); mgr.flush()\n")
    rc = subprocess.run([sys.executable, "-c", seed], env=ENV,
                        capture_output=True, text=True, timeout=300)
    assert rc.returncode == 0, rc.stderr[-2000:]

    cli = [sys.executable, os.path.join(REPO, "tools", "ckpt.py")]
    ok = subprocess.run([*cli, "verify", ckdir, "--json"], env=ENV,
                        capture_output=True, text=True, timeout=300)
    assert ok.returncode == 0, ok.stderr[-2000:]
    report = json.loads(ok.stdout)
    assert report["ok"] and report["step"] == 7 and report["arrays"] >= 3

    listing = subprocess.run([*cli, "list", ckdir], env=ENV,
                             capture_output=True, text=True, timeout=300)
    assert listing.returncode == 0 and "7" in listing.stdout

    # corrupt a payload stretch (wide enough to guarantee it hits array
    # data, not zip alignment padding): verify must fail with exit code 1
    npz = os.path.join(ckdir, "step-00000007", "arrays.npz")
    with open(npz, "r+b") as f:
        f.seek(os.path.getsize(npz) // 2)
        chunk = bytearray(f.read(256))
        f.seek(-len(chunk), os.SEEK_CUR)
        f.write(bytes(b ^ 0xFF for b in chunk))
    bad = subprocess.run([*cli, "verify", ckdir, "--step", "7"], env=ENV,
                         capture_output=True, text=True, timeout=300)
    assert bad.returncode == 1, (bad.stdout, bad.stderr)

    empty = subprocess.run([*cli, "verify", str(tmp_path / "none")],
                           env=ENV, capture_output=True, text=True,
                           timeout=300)
    assert empty.returncode == 2


def test_blackbox_numerics_bundle_smoke(tmp_path):
    """Induce a NaN under MXTPU_NUMERICS=step: the postmortem bundle
    must hold the bisected equation, and tools/blackbox.py must render
    it in the report + a valid chrome trace (docs/observability.md)."""
    script = (
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import observability\n"
        "from mxnet_tpu.gluon import Trainer, TrainStep, nn\n"
        "net = nn.HybridSequential()\n"
        "net.add(nn.Dense(16, activation='relu'), nn.Dense(4))\n"
        "net.initialize(); net.hybridize()\n"
        "tr = Trainer(net.collect_params(), 'sgd',\n"
        "             {'learning_rate': 0.05})\n"
        "step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), tr)\n"
        "x = mx.np.array(onp.ones((8, 12), 'float32'))\n"
        "y = mx.np.zeros((8, 4))\n"
        "step(x, y)\n"
        "xbad = mx.np.array(onp.full((8, 12), onp.nan, 'float32'))\n"
        "try:\n"
        "    step(xbad, y)\n"
        "except observability.NonFiniteError as e:\n"
        "    print(e.bundle)\n"
        "else:\n"
        "    raise SystemExit('NaN step did not trip')\n")
    rc = subprocess.run(
        [sys.executable, "-c", script],
        env=dict(ENV, MXTPU_NUMERICS="step",
                 MXTPU_FLIGHTREC_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert rc.returncode == 0, rc.stderr[-2000:]
    bundle_path = rc.stdout.strip().split("\n")[-1]
    assert os.path.exists(bundle_path), bundle_path
    bundle = json.load(open(bundle_path))
    assert bundle["reason"] == "numerics"
    assert bundle["numerics_bisect"]["op"]  # the bisected equation
    assert bundle["numerics_bisect"]["operands"]

    trace_out = str(tmp_path / "merged.trace.json")
    bb = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox.py"),
         bundle_path, "--trace", trace_out],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert bb.returncode == 0, bb.stderr[-2000:]
    assert "numerics bisect" in bb.stdout
    assert bundle["numerics_bisect"]["op"] in bb.stdout
    trace = json.load(open(trace_out))
    assert trace["traceEvents"]
    assert any(e.get("name") == "numerics_trip"
               for e in trace["traceEvents"])


def test_blackbox_merges_sigkilled_ranks(tmp_path):
    """The black-box acceptance path: two ranks train with the periodic
    flight-recorder spill on, get SIGKILL'd mid-run, and blackbox.py
    merges the surviving per-rank bundles into one step-aligned chrome
    trace + stall report."""
    import signal
    import time

    script = (
        "import time\n"
        "import numpy as onp\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import autograd, gluon\n"
        "net = gluon.nn.Dense(4); net.initialize()\n"
        "tr = gluon.Trainer(net.collect_params(), 'sgd',\n"
        "                   {'learning_rate': 0.1})\n"
        "x = mx.np.array(onp.ones((2, 3), 'float32'))\n"
        "for _ in range(3):\n"
        "    with autograd.record():\n"
        "        loss = (net(x) ** 2).mean()\n"
        "    loss.backward()\n"
        "    tr.step(2)\n"
        "mx.waitall()\n"
        "while True:\n"       # hang until the parent SIGKILLs us
        "    time.sleep(0.5)\n")
    procs = []
    try:
        for r in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", script],
                env=dict(ENV, MXTPU_FLIGHTREC_RANK=str(r),
                         MXTPU_JOB_ID="blackbox-test",
                         MXTPU_FLIGHTREC_FLUSH_STEPS="1",
                         MXTPU_FLIGHTREC_DIR=str(tmp_path)),
                stdout=subprocess.DEVNULL,
                # a file, not a pipe: nobody reads while the worker runs,
                # and a full pipe would block it
                stderr=open(tmp_path / f"stderr{r}", "wb")))
        paths = [str(tmp_path / f"mxtpu_blackbox.rank{r}.json")
                 for r in range(2)]

        def _complete(p):
            # the spill is async; wait for a bundle showing all 3 steps
            try:
                b = json.load(open(p))
                return any(e.get("step", 0) >= 2 for e in b["events"])
            except (OSError, ValueError, KeyError):
                return False

        deadline = time.monotonic() + 240
        while not all(_complete(p) for p in paths):
            for r, pr in enumerate(procs):
                if pr.poll() is not None:
                    err = (tmp_path / f"stderr{r}").read_text()
                    raise AssertionError(f"worker died: {err[-2000:]}")
            assert time.monotonic() < deadline, "bundles never appeared"
            time.sleep(0.25)
    finally:
        for pr in procs:
            if pr.poll() is None:
                os.kill(pr.pid, signal.SIGKILL)
            pr.wait()

    trace_out = str(tmp_path / "merged.trace.json")
    report_out = str(tmp_path / "report.txt")
    bb = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "blackbox.py"),
         *paths, "--trace", trace_out, "--report", report_out],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert bb.returncode == 0, bb.stderr[-2000:]

    trace = json.load(open(trace_out))
    assert trace["metadata"]["ranks"] == [0, 1]
    # step-aligned: both ranks shared a span anchor for a common step
    assert trace["metadata"]["aligned_on_step"] is not None
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {0, 1}
    names = {e["name"] for e in trace["traceEvents"]}
    assert "step" in names            # flight heartbeat from both ranks
    assert "optimizer_update" in names  # span records made it across

    report = open(report_out).read()
    assert "job 'blackbox-test', 2 rank(s)" in report
    assert "rank 0:" in report and "rank 1:" in report
    assert "each rank was doing" in report


def test_crash_bundle_reason_survives_exit(tmp_path):
    """An uncaught exception must leave a bundle whose reason carries the
    exception class — the atexit "exit" dump must not overwrite it."""
    script = r"""
import jax; jax.config.update("jax_platforms", "cpu")
import mxnet_tpu as mx
from mxnet_tpu import observability
assert observability.postmortem.crash_hooks_installed()
observability.flight.record("tick")
raise RuntimeError("boom")
"""
    env = dict(ENV, MXTPU_FLIGHTREC_CRASHDUMP="1",
               MXTPU_FLIGHTREC_DIR=str(tmp_path),
               MXTPU_FLIGHTREC_RANK="0")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0  # the crash must still propagate
    b = json.load(open(tmp_path / "mxtpu_blackbox.rank0.json"))
    assert b["reason"] == "crash:RuntimeError", b["reason"]
    kinds = [e["kind"] for e in b["events"]]
    assert "crash" in kinds and "tick" in kinds


# -- fleetctl + diagnose --live against live ops servers ---------------------

_WORKER = """
import os, sys, time
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu.gluon import Trainer, TrainStep, nn
from mxnet_tpu.observability import opsd

steps, portfile = int(sys.argv[1]), sys.argv[2]
srv = opsd.start(port=0)
net = nn.HybridSequential()
net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
net.initialize(); net.hybridize()
trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), trainer)
rs = onp.random.RandomState(0)
x = mx.np.array(rs.rand(8, 12).astype("f"))
y = mx.np.array(rs.rand(8, 4).astype("f"))
for _ in range(steps):
    step(x, y)
mx.waitall()
with open(portfile + ".tmp", "w") as f:
    f.write(str(srv.port))
os.replace(portfile + ".tmp", portfile)   # port visible only when ready
deadline = time.time() + 180
while not os.path.exists(portfile + ".stop") and time.time() < deadline:
    time.sleep(0.05)
"""


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """Two concurrently running rank servers of one job, with skewed
    step counts (rank 0 at step 8, rank 1 at step 2) so straggler
    detection has something to find."""
    tmp = tmp_path_factory.mktemp("fleet")
    script = tmp / "worker.py"
    script.write_text(_WORKER)
    procs, ports = [], {}
    try:
        for rank, steps in ((0, 8), (1, 2)):
            portfile = str(tmp / f"port{rank}")
            env = dict(ENV, MXTPU_FLIGHTREC_RANK=str(rank),
                       MXTPU_JOB_ID="fleetjob",
                       MXTPU_FLIGHTREC_DIR=str(tmp))
            procs.append(subprocess.Popen(
                [sys.executable, str(script), str(steps), portfile],
                env=env, stdout=subprocess.DEVNULL,
                stderr=open(tmp / f"stderr{rank}", "wb")))
        deadline = time.time() + 180
        for rank in (0, 1):
            portfile = str(tmp / f"port{rank}")
            while not os.path.exists(portfile):
                if time.time() > deadline:
                    raise RuntimeError(
                        f"rank {rank} never published its port: "
                        + (tmp / f"stderr{rank}").read_text()[-2000:])
                if procs[rank].poll() is not None:
                    raise RuntimeError(
                        f"rank {rank} died: "
                        + (tmp / f"stderr{rank}").read_text()[-2000:])
                time.sleep(0.05)
            ports[rank] = int(open(portfile).read())
        yield {"tmp": tmp, "ports": ports}
    finally:
        for rank in (0, 1):
            open(str(tmp / f"port{rank}") + ".stop", "w").close()
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()


def test_fleetctl_table_flags_straggler(fleet):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import fleetctl

    eps = [f"127.0.0.1:{fleet['ports'][r]}" for r in (0, 1)]
    rows = fleetctl.annotate_stragglers(
        [fleetctl.poll_rank(ep) for ep in eps], skew=2)
    by_rank = {r["rank"]: r for r in rows}
    assert set(by_rank) == {0, 1}
    assert all(r["job"] == "fleetjob" for r in rows)
    assert by_rank[0]["last_step"] >= 8 and by_rank[1]["last_step"] <= 2
    assert not by_rank[0]["straggler"]
    assert by_rank[1]["straggler"]

    table = fleetctl.fleet_table(rows)
    assert "STRAGGLER" in table
    assert "job=fleetjob" in table and "stragglers=1" in table

    # CLI: exit code 2 signals stragglers; --json carries the rows
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleetctl.py"),
         *eps, "--json"],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert rc.returncode == 2, rc.stderr[-2000:]
    out = json.loads(rc.stdout)
    assert sum(1 for r in out if r["straggler"]) == 1

    # a down endpoint still gets a row, flagged
    rows = fleetctl.annotate_stragglers(
        [fleetctl.poll_rank(ep) for ep in eps]
        + [fleetctl.poll_rank("127.0.0.1:9", timeout=1.0)], skew=2)
    down = [r for r in rows if r["health"] == "down"]
    assert down and down[0]["straggler"]


def test_fleetctl_postmortem_all_feeds_blackbox(fleet):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import blackbox
    import fleetctl

    eps = [f"127.0.0.1:{fleet['ports'][r]}" for r in (0, 1)]
    paths = fleetctl.postmortem_all(eps, timeout=60)
    assert len(paths) == 2
    assert not any(str(p).startswith("ERROR") for p in paths.values()), paths

    bundles = [blackbox.load_bundle(p) for p in sorted(set(paths.values()))]
    assert len(bundles) == 2
    assert {b["identity"]["rank"] for b in bundles} == {0, 1}
    text = blackbox.report(bundles)
    assert "fleetjob" in text
    assert "STRAGGLER" in text  # rank 1's lower last step

    # the CLI one-shot: --postmortem-all --merge
    prefix = str(fleet["tmp"] / "merged")
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleetctl.py"),
         *eps, "--postmortem-all", "--merge", prefix],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, rc.stderr[-2000:]
    assert os.path.exists(prefix + ".trace.json")
    assert os.path.exists(prefix + ".report.txt")


def test_diagnose_live_mode(fleet):
    """tools/diagnose.py --live renders the report from a running rank's
    ops server — no workload, no jax import on the client side."""
    ep = f"127.0.0.1:{fleet['ports'][0]}"
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py"),
         "--live", ep],
        env=dict(ENV, JAX_PLATFORMS=""), capture_output=True, text=True,
        timeout=120)
    assert rc.returncode == 0, rc.stderr[-2000:]
    assert "== live diagnostics: rank 0" in rc.stdout
    assert "== per-step phase breakdown ==" in rc.stdout
    assert "== telemetry (scraped /metrics) ==" in rc.stdout
    assert "== flight tail ==" in rc.stdout

    rj = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py"),
         "--live", ep, "--json"],
        env=ENV, capture_output=True, text=True, timeout=120)
    assert rj.returncode == 0, rj.stderr[-2000:]
    doc = json.loads(rj.stdout)
    assert doc["identity"]["rank"] == 0
    assert doc["steps"]["last_step"] >= 8
    assert "step_total" in doc["metrics"]
