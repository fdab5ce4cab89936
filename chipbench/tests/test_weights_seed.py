"""The seed contract (PR 46): ``--seed`` draws the traffic, and the
weights too unless the configuration states ONE draw of them
(``weights_seed``).  On the CPU at the toy sizes: every driver and the
large cells' control make their weights by that one rule, the reference's
regenerated weights are the program's, files without the key behave bit
for bit as before, only the SDAR configuration states a draw, and the
control's numbers read ``correct`` false against a cell's limits.  And
the stated pool (``traffic_params.pool_seed``, the cure the check asked
of the SDAR cell): every ``--seed`` meets the same batches in another
order, in the run and in the control alike."""
import contextlib
import glob
import importlib
import json
import os

import numpy as onp
import pytest

import control
import control_large
import run as harness
import weights as wmod
from conftest import BENCH, ROOT

DRAW = 2 ** 32 + 77             # a stated draw, past 32 bits as the driver's
PRESETS = ["toy_train", "toy_train_bert", "toy_train_sdar",
           "toy_train_kanana2", "toy_train_ouro", "toy_train_loader"]


def _files(preset, **extra):
    wl = harness._load_json("workloads", preset + ".json")
    cfg = dict(harness._load_json("configs", wl["config"] + ".json"),
               **extra)
    return wl, cfg


def _host(tree):
    return {n: onp.asarray(w) for n, w in tree.items()}


def _same(a, b):
    return a.keys() == b.keys() and all(
        a[n].dtype == b[n].dtype and onp.array_equal(a[n], b[n]) for n in a)


@pytest.fixture
def spy(monkeypatch):
    """Every call of `weights.make_weights` / `make_batches`: the seed it
    got and, for the weights, what it returned."""
    make_weights, make_batches = wmod.make_weights, wmod.make_batches
    seen = {"weights": [], "batches": [], "make_weights": make_weights,
            "make_batches": make_batches}

    def weights(specs, seed, low_dtype):
        out = make_weights(specs, seed, low_dtype)
        seen["weights"].append((seed, _host(out)))
        return out

    def batches(input_specs, seed, pool):
        out = make_batches(input_specs, seed, pool)
        seen["batches"].append((seed, [tuple(onp.asarray(a) for a in bt)
                                       for bt in out]))
        return out

    monkeypatch.setattr(wmod, "make_weights", weights)
    monkeypatch.setattr(wmod, "make_batches", batches)
    return seen


def test_the_rule():
    assert wmod.weights_seed({}, 5) == 5
    assert wmod.weights_seed({"weights_seed": DRAW}, 5) == DRAW
    assert wmod.weights_seed({"weights_seed": 0}, 5) == 0


# -- every driver, up to the point where the program is built ----------------

class _Built(Exception):
    """Raised in place of building the program: the weights are made."""


class _Harness:
    def __init__(self, wl, cfg, seed):
        self.workload, self.cfg, self.seed = wl, cfg, seed
        self.traffic = wl["traffic_params"]
        self.reference = importlib.import_module(
            "reference." + cfg["builder"])
        self.notes, self.trace = {}, False

    @contextlib.contextmanager
    def span(self, name, compile=False):
        yield

    def note(self, **kv):
        self.notes.update(kv)


def _weights_a_driver_hands_over(monkeypatch, wl, cfg, seed):
    """(the weights the driver gives the program, the harness)."""
    handed = []

    def program(h, weights):
        handed.append(_host(weights))
        raise _Built

    # both imported before either is patched: the second takes the name
    # from the first as it is imported
    for module in [importlib.import_module("drivers." + name)
                   for name in ("train_step", "train_step_large")]:
        monkeypatch.setattr(module, "Program", program)
    h = _Harness(wl, cfg, seed)
    with pytest.raises(_Built):
        importlib.import_module("drivers." + wl["driver"]).run(h)
    return handed[0], h


@pytest.mark.parametrize("preset", PRESETS)
def test_without_the_key_a_seed_gives_what_it_gave(monkeypatch, spy, preset):
    """Weights and batches bit-equal to `make_weights(specs, seed, ...)` /
    `make_batches(input_specs, seed, pool)`: the three cells that state no
    draw are untouched."""
    wl, cfg = _files(preset)
    assert "weights_seed" not in cfg
    seed = 2 ** 31 + 11
    got, h = _weights_a_driver_hands_over(monkeypatch, wl, cfg, seed)
    ref, tp = h.reference, wl["traffic_params"]
    assert [s for s, _ in spy["weights"]] == [seed]
    assert h.notes["weights_seed"] == seed
    assert _same(got, _host(spy["make_weights"](
        ref.param_specs(cfg), seed, cfg["dtype"])))
    if wl["driver"] != "train_loader":      # the loader draws its own
        (s, drawn), = spy["batches"]
        want = spy["make_batches"](ref.input_specs(cfg, tp["batch"]), seed,
                                   tp["pool"])
        assert s == seed and len(drawn) == len(want) == tp["pool"]
        assert all(onp.array_equal(x, onp.asarray(y))
                   for bx, by in zip(drawn, want) for x, y in zip(bx, by))


@pytest.mark.parametrize("preset", PRESETS)
def test_a_stated_draw_fixes_the_weights_and_not_the_traffic(
        monkeypatch, spy, preset):
    wl, cfg = _files(preset, weights_seed=DRAW)
    runs = [_weights_a_driver_hands_over(monkeypatch, wl, cfg, seed)
            for seed in (2 ** 31 + 11, 2 ** 31 + 12)]
    (w1, h1), (w2, h2) = runs
    assert _same(w1, w2)
    assert [s for s, _ in spy["weights"]] == [DRAW, DRAW]
    assert h1.notes["weights_seed"] == h2.notes["weights_seed"] == DRAW
    # ... and they are the draw's own weights, not the run's seed's
    specs = h1.reference.param_specs(cfg)
    by_seed = _host(spy["make_weights"](specs, 2 ** 31 + 11, cfg["dtype"]))
    assert not _same(w1, by_seed)
    if wl["driver"] != "train_loader":
        (s1, b1), (s2, b2) = spy["batches"]
        assert (s1, s2) == (2 ** 31 + 11, 2 ** 31 + 12)
        assert any(not onp.array_equal(x, y)
                   for bx, by in zip(b1, b2) for x, y in zip(bx, by))


# -- a whole toy run: the reference makes the program's weights again --------

def _run(capsys, monkeypatch, cfg_extra, seed, traffic_extra=None):
    load = harness._load_json

    def load_json(*parts):
        d = load(*parts)
        if parts[0] == "workloads" and traffic_extra:
            return dict(d, traffic_params=dict(d["traffic_params"],
                                               **traffic_extra))
        return dict(d, **cfg_extra) if parts[0] == "configs" else d

    monkeypatch.setattr(harness, "_load_json", load_json)
    monkeypatch.setenv("PYTHONHASHSEED", "0")     # no re-exec inside a test
    rc = harness.main(["--workload", "toy_train_sdar", "--seed", str(seed),
                       "--seconds", "1.0", "--trace", "0"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    notes = next(l["notes"] for l in lines if "notes" in l)
    return rc, lines[-1], notes


@pytest.mark.parametrize("extra, seed, draw", [
    ({"weights_seed": DRAW}, 21, DRAW), ({"weights_seed": DRAW}, 22, DRAW),
    ({}, 21, 21)])
def test_the_reference_regenerates_the_programs_weights(
        capsys, monkeypatch, spy, extra, seed, draw):
    """Through `run.py`: the program's weights, the reference's at its
    start and the reference's for the comparison at its end are three
    calls of one closure, all of the one draw; the run is `correct` and
    its notes say which weights it was."""
    rc, result, notes = _run(capsys, monkeypatch, extra, seed)
    assert rc == 0 and result["correct"] is True, result
    assert notes["weights_seed"] == draw
    assert [s for s, _ in spy["weights"]] == [draw] * 3
    first = spy["weights"][0][1]
    assert all(_same(first, w) for _, w in spy["weights"][1:])
    assert [s for s, _ in spy["batches"]] == [seed]


# -- the control -------------------------------------------------------------

@pytest.mark.parametrize("extra", [{"weights_seed": DRAW}, {}])
def test_the_large_control_makes_its_weights_by_the_same_rule(spy, extra):
    wl, cfg = _files("toy_train_sdar", **extra)
    nums = control_large.control_numbers(wl, cfg, 5)
    draw = extra.get("weights_seed", 5)
    assert [s for s, _ in spy["weights"]] == [draw] * 4
    assert [s for s, _ in spy["batches"]] == [5]
    # ... and through the run's own comparison it reads not correct
    correct, over = control.verdict(cfg, nums)
    assert correct is False and "grad_norm_gap.weights_median" in over


def test_a_sound_reading_is_correct_by_the_controls_verdict():
    _, cfg = _files("toy_train_sdar")
    limits = cfg["limits"]["train_step"]
    nums = {"loss_rel.step%d" % (i + 1): v / 2
            for i, v in enumerate(limits["loss_rel"])}
    nums.update({n: v / 2 for n, v in limits.items()
                 if isinstance(v, float)}, _leaves={}, **{"x.leaf": "w"})
    assert control.verdict(cfg, nums) == (True, [])


# -- a stated pool: the same batches for every seed, in another order --------

POOL = 2 ** 31 + 5              # a stated pool's seed
SEEDS = [3, 2 ** 31 + 11, 2 ** 32 + 12, 4600000701]


@pytest.mark.parametrize("pool", [3, 5, 8])
def test_the_order_is_drawn_from_the_seed_the_same_batches_first(pool):
    orders = [wmod.pool_order(seed, pool, 3) for seed in SEEDS]
    assert all(sorted(o) == list(range(pool)) for o in orders)
    assert all(sorted(o[:3]) == [0, 1, 2] for o in orders)
    assert orders == [wmod.pool_order(seed, pool, 3) for seed in SEEDS]
    drawn = {tuple(wmod.pool_order(seed, pool, 3)) for seed in range(400)}
    # every order of the first three, times every order of the others
    assert len(drawn) == {3: 6, 5: 12}.get(pool, len(drawn)) > 5


@pytest.mark.parametrize("preset", ["toy_train", "toy_train_bert",
                                    "toy_train_sdar", "toy_train_kanana2",
                                    "toy_train_ouro"])
def test_a_stated_pool_is_met_in_the_seeds_order(spy, preset):
    wl, cfg = _files(preset)
    ref = importlib.import_module("reference." + cfg["builder"])
    tp = dict(wl["traffic_params"], pool=5, pool_seed=POOL)
    batches = importlib.import_module(
        "drivers." + wl["driver"]).reference_batches
    want = spy["make_batches"](ref.input_specs(cfg, tp["batch"]), POOL, 5)
    for seed in SEEDS:
        for n in (3, 5):
            got = batches(cfg, tp, seed, n, ref)
            order = wmod.pool_order(seed, 5, 3)[:n]
            assert len(got) == n
            assert all(onp.array_equal(onp.asarray(x), onp.asarray(y))
                       for bt, k in zip(got, order)
                       for x, y in zip(bt, want[k]))
    # the pool is made from its own seed, whole, whatever --seed and n
    assert {(s, len(b)) for s, b in spy["batches"]} == {(POOL, 5)}


@pytest.mark.parametrize("seed", [21, 22, 2 ** 31 + 23])
def test_a_run_on_a_stated_pool(capsys, monkeypatch, spy, seed):
    """Through `run.py`: `correct` on the stated pool in the seed's order,
    which the notes carry; the program and the reference see one pool."""
    rc, result, notes = _run(capsys, monkeypatch, {"weights_seed": DRAW},
                             seed, {"pool": 4, "pool_seed": POOL})
    assert rc == 0 and result["correct"] is True, result
    assert notes["pool_seed"] == POOL
    assert notes["pool_order"] == wmod.pool_order(seed, 4, 3)
    assert [s for s, _ in spy["batches"]] == [POOL]


def test_the_large_control_meets_the_stated_pool_in_the_seeds_order(spy):
    wl, cfg = _files("toy_train_sdar")
    wl = dict(wl, traffic_params=dict(wl["traffic_params"], pool=4,
                                      pool_seed=POOL))
    a = control_large.control_numbers(wl, cfg, 5)
    assert [(s, len(b)) for s, b in spy["batches"]] == [(POOL, 4)]
    b = control_large.control_numbers(wl, cfg, 6)
    assert wmod.pool_order(5, 4, 3)[:3] != wmod.pool_order(6, 4, 3)[:3]
    assert a["loss_rel.step1"] != b["loss_rel.step1"]
    assert control.verdict(cfg, a)[0] is False


def test_only_the_sdar_cell_states_its_pool():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"] for w in json.load(f)["workloads"]}
    stated = {}
    for path in glob.glob(os.path.join(BENCH, "workloads", "*.json")):
        with open(path) as f:
            wl = json.load(f)
        if "pool_seed" in wl["traffic_params"]:
            stated[wl["name"]] = wl
    assert set(stated) == {"sdar_30b_a3b.train.blockdiff.b2s4096"} <= cells
    wl = stated["sdar_30b_a3b.train.blockdiff.b2s4096"]
    tp = wl["traffic_params"]
    # a window is a whole number of tens of steps (the fetch paces it): a
    # pool that divides ten is met equally often in every order
    assert 10 % tp["pool"] == 0 and tp["pool"] >= 3
    assert isinstance(tp["pool_seed"], int) and tp["pool_seed"] >= 0
    for words in ("pool_seed", "--seed draws the ORDER",
                  "the same set of work in another order",
                  "the pool's first three batches"):
        assert words in wl["why"], words


# -- which configuration states a draw ----------------------------------------

def test_only_the_sdar_configuration_states_a_draw():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {c["name"] for c in bench["configs"]}
    stated = {}
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        with open(path) as f:
            cfg = json.load(f)
        if "weights_seed" in cfg:
            stated[cfg["name"]] = cfg
    assert set(stated) == {"sdar_30b_a3b_ep8"} and set(stated) <= listed
    cfg = stated["sdar_30b_a3b_ep8"]
    draw = cfg["weights_seed"]
    assert isinstance(draw, int) and not isinstance(draw, bool) and draw >= 0
    assert "weights_seed" not in cfg["reduced"]
    # how it was chosen stands beside it
    chosen = cfg["assumed"]["weights_seed"]
    assert str(draw) in chosen and "median" in chosen
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    cell = next(w for w in bench["workloads"] if w["config"] == cfg["name"])
    wl = harness._load_json("workloads", cell["name"] + ".json")
    for why in (entry["why"], cell["why"], wl["why"]):
        assert "--seed" in why and "draw" in why, why
