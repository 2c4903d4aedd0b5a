"""TPE searcher + median stopping rule (reference test model:
python/ray/tune/tests/test_searchers.py, test_trial_scheduler.py
median-stopping cases)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune.schedulers import CONTINUE, STOP, MedianStoppingRule
from ray_tpu.tune.search import TPESearcher


@pytest.fixture(scope="module")
def cluster(native_store):
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


# ------------------------------------------------------------------- TPE

def _sphere_score(x: float, y: float) -> float:
    """Unimodal quadratic (negated: higher is better); optimum 0 at
    (2, -3)."""
    return -((x - 2.0) ** 2 + (y + 3.0) ** 2)


def test_tpe_beats_random_on_seeded_objective():
    """Seeded A/B: mean best-of-40 over 8 seeds — TPE must beat pure
    random sampling (the VERDICT 'BO beats random' gate)."""
    import random as _random

    def space():
        return {"x": tune.uniform(-10.0, 10.0),
                "y": tune.uniform(-10.0, 10.0)}

    tpe_bests, rnd_bests = [], []
    for seed in range(8):
        searcher = TPESearcher(n_initial=10, seed=seed)
        searcher.set_search_properties("score", "max", space())
        best = -np.inf
        for i in range(40):
            tid = f"t{i}"
            cfg = searcher.suggest(tid)
            score = _sphere_score(cfg["x"], cfg["y"])
            searcher.on_trial_complete(tid, {"score": score})
            best = max(best, score)
        tpe_bests.append(best)
        rng = _random.Random(seed)
        sp = space()
        rnd_bests.append(max(
            _sphere_score(sp["x"].sample(rng), sp["y"].sample(rng))
            for _ in range(40)))
    assert np.mean(tpe_bests) > np.mean(rnd_bests), \
        (tpe_bests, rnd_bests)


def test_tpe_handles_categorical_int_log():
    space = {
        "opt": tune.choice(["adam", "sgd"]),
        "layers": tune.randint(1, 5),
        "lr": tune.loguniform(1e-5, 1e-1),
    }
    searcher = TPESearcher(n_initial=5, seed=0)
    searcher.set_search_properties("score", "max", space)
    # Objective: adam + lr near 1e-3 + layers=3 wins.
    import math

    for i in range(30):
        tid = f"t{i}"
        cfg = searcher.suggest(tid)
        assert cfg["opt"] in ("adam", "sgd")
        assert 1 <= cfg["layers"] < 5
        assert 1e-5 <= cfg["lr"] <= 1e-1
        score = ((1.0 if cfg["opt"] == "adam" else 0.0)
                 - abs(math.log10(cfg["lr"]) + 3.0)
                 - abs(cfg["layers"] - 3) * 0.2)
        searcher.on_trial_complete(tid, {"score": score})
    # The searcher's model should now prefer adam strongly.
    suggestions = [searcher.suggest(f"p{i}") for i in range(10)]
    adam_frac = sum(c["opt"] == "adam" for c in suggestions) / 10
    assert adam_frac >= 0.6, adam_frac


def test_tpe_state_roundtrip():
    s1 = TPESearcher(n_initial=2, seed=0)
    space = {"x": tune.uniform(0.0, 1.0)}
    s1.set_search_properties("score", "max", space)
    for i in range(6):
        tid = f"t{i}"
        cfg = s1.suggest(tid)
        s1.on_trial_complete(tid, {"score": cfg["x"]})
    state = s1.get_state()
    s2 = TPESearcher(n_initial=2, seed=0)
    s2.set_search_properties("score", "max", space)
    s2.set_state(state)
    assert len(s2._obs) == 6
    cfg = s2.suggest("t9")  # model-based immediately (past n_initial)
    assert 0.0 <= cfg["x"] <= 1.0


# -------------------------------------------------------- median stopping

def test_median_stopping_prunes_loser():
    rule = MedianStoppingRule("acc", grace_period=2,
                              min_samples_required=2)
    # 3 trials: a,b strong; c weak. Feed 4 rounds.
    for it in range(1, 5):
        batch = [("a", it, {"acc": 0.9}), ("b", it, {"acc": 0.8}),
                 ("c", it, {"acc": 0.1})]
        decisions = rule.on_batch(batch)
        if it < 2:
            assert decisions["c"] == CONTINUE  # grace
        if it >= 2:
            assert decisions["a"] == CONTINUE
            assert decisions["b"] == CONTINUE
    assert decisions["c"] == STOP


def test_median_stopping_no_stop_below_min_samples():
    rule = MedianStoppingRule("acc", grace_period=0,
                              min_samples_required=5)
    decisions = rule.on_batch([("a", 3, {"acc": 0.0}),
                               ("b", 3, {"acc": 1.0})])
    assert decisions["a"] == CONTINUE  # only 1 other trial reported


# -------------------------------------------------------------- end-to-end

def test_tuner_with_tpe_and_median_stopping(cluster, tmp_path):
    """Full Tuner.fit with the searcher + median stopping: the best found
    config must land near the objective's optimum, and the searcher state
    must be in the experiment snapshot."""
    import json

    def objective(config):
        for _ in range(3):
            tune.report({"score": -(config["x"] - 2.0) ** 2})

    class RC:
        storage_path = str(tmp_path)
        name = "tpe_exp"

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.uniform(-10.0, 10.0)},
        tune_config=tune.TuneConfig(
            metric="score", mode="max", num_samples=25,
            max_concurrent_trials=3,
            search_alg=TPESearcher(n_initial=8, seed=3),
            scheduler=MedianStoppingRule("score", grace_period=1)),
        run_config=RC())
    grid = tuner.fit()
    best = grid.get_best_result()
    assert abs(best.config["x"] - 2.0) < 2.5, best.config
    state = json.loads(
        (tmp_path / "tpe_exp" / "experiment_state.json").read_text())
    assert state.get("searcher", {}).get("obs"), "searcher state missing"


def test_hyperband_brackets_and_halving():
    """Unit: bracket assignment round-robins; a full cohort at a rung
    keeps the top 1/eta and stops the rest; trials at max_t stop."""
    from ray_tpu.tune.schedulers import HyperBandScheduler

    hb = HyperBandScheduler("acc", max_t=9, reduction_factor=3)
    # 3 brackets (s_max=2): trials deal round-robin.
    for i in range(6):
        hb.register(f"t{i}", {})
    assert hb._trial_bracket["t0"] != hb._trial_bracket["t1"] or \
        hb._s_max == 0
    # Pick the bracket with the MOST rungs (t0's bracket 0 has only the
    # final rung, which is never halved — asserting on it is dead code).
    b = max(hb._bracket_rungs, key=lambda bb: len(hb._bracket_rungs[bb]))
    cohort = [t for t, bb in hb._trial_bracket.items() if bb == b]
    rungs = hb._bracket_rungs[b]
    assert len(rungs) > 1 and len(cohort) >= 2, (rungs, cohort)
    rung = rungs[0]
    batch = [(t, rung, {"acc": float(i)}) for i, t in enumerate(cohort)]
    decisions = hb.on_batch(batch)
    stops = [t for t, d in decisions.items() if d == "STOP"]
    keeps = [t for t, d in decisions.items() if d == "CONTINUE"]
    assert keeps and stops  # halving happened
    # The kept trial(s) scored highest.
    best = max(cohort, key=lambda t: hb._scores[t][rung])
    assert best in keeps
    # on_result protocol: a judged-out loser learns its STOP on its next
    # report (straggler decisions are never lost).
    assert hb.on_result(stops[0], rung + 1, {"acc": 99.0}) == "STOP"
    # max_t always stops.
    d = hb.on_batch([("t0", 9, {"acc": 1.0})])
    assert d["t0"] == "STOP"


def test_bohb_models_highest_adequate_fidelity():
    """Unit: with mixed-budget observations, BOHB builds its TPE model
    from the highest budget tier holding >= n_initial points."""
    from ray_tpu.tune.search import BOHBSearcher

    s = BOHBSearcher(n_initial=4, seed=0)
    s.set_search_properties("score", "max",
                            {"x": tune.uniform(0.0, 1.0)})
    # 3 high-budget (not enough), 6 low-budget (enough).
    for i in range(3):
        tid = f"hi{i}"
        s._live[tid] = {"x": 0.9}
        s.on_trial_complete(tid, {"score": 1.0, "training_iteration": 9})
    for i in range(6):
        tid = f"lo{i}"
        s._live[tid] = {"x": 0.1 + 0.01 * i}
        s.on_trial_complete(tid, {"score": 0.5, "training_iteration": 1})
    model = s._model_obs()
    # Tier budget>=1 is the highest tier with >= 4 points (all 9 obs).
    assert len(model) == 9
    # Add high-budget points until that tier suffices on its own.
    s._live["hi3"] = {"x": 0.91}
    s.on_trial_complete("hi3", {"score": 1.1, "training_iteration": 9})
    model = s._model_obs()
    assert len(model) == 4 and all(o["budget"] >= 9 for o in model)
    # Suggestions remain in-domain.
    cfg = s.suggest("t-new")
    assert 0.0 <= cfg["x"] <= 1.0


def test_bohb_with_hyperband_end_to_end(cluster):
    """BOHB pairing: HyperBand prunes, BOHB suggests from mixed-fidelity
    completions, best region is found on a seeded quadratic."""
    from ray_tpu.tune.search import BOHBSearcher

    def objective(config):
        for step in range(3):
            tune.report({"acc": _sphere_score(config["x"], -3.0)})

    tuner = tune.Tuner(
        objective,
        param_space={"x": tune.uniform(-10.0, 10.0)},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max", num_samples=16,
            max_concurrent_trials=4,
            search_alg=BOHBSearcher(n_initial=6, seed=1),
            scheduler=tune.HyperBandScheduler("acc", max_t=3,
                                              reduction_factor=3)))
    grid = tuner.fit()
    best = grid.get_best_result()
    assert abs(best.config["x"] - 2.0) < 3.0, best.config
    # The REAL integration feeds fidelities: observations must carry the
    # iteration each trial reached, not all land in a budget-0 tier.
    searcher = tuner._cfg.search_alg
    assert searcher._obs and any(o["budget"] > 0 for o in searcher._obs), \
        searcher._obs[:3]


# ------------------------------------------------------------------- PB2

def test_pb2_explore_proposes_in_bounds_and_exploits_gp():
    from ray_tpu.tune.schedulers import PB2

    pb2 = PB2("score", perturbation_interval=2,
              hyperparam_bounds={"lr": [0.0, 1.0]}, seed=0)
    # Cold start: uniform within bounds.
    cfg = pb2._explore({"lr": 0.5})
    assert 0.0 <= cfg["lr"] <= 1.0
    # Seed the GP: improvements peak sharply around lr=0.8.
    for v in np.linspace(0.0, 1.0, 20):
        pb2._gp_data.append(([float(v)],
                             float(np.exp(-50 * (v - 0.8) ** 2))))
    props = [pb2._explore({"lr": 0.1})["lr"] for _ in range(8)]
    assert all(0.0 <= p <= 1.0 for p in props)
    # The GP-UCB argmax should concentrate near the peak on average.
    assert abs(float(np.mean(props)) - 0.8) < 0.25, props


def test_pb2_validates_bounds():
    from ray_tpu.tune.schedulers import PB2

    with pytest.raises(ValueError, match="non-empty"):
        PB2("score", hyperparam_bounds={})
    with pytest.raises(ValueError, match="low, high"):
        PB2("score", hyperparam_bounds={"lr": [1.0, 0.5]})


def test_pb2_clones_and_explores_bottom_trials():
    """Scheduler protocol: bottom trial at the interval gets a clone
    decision whose config came from the GP explore, inside bounds."""
    from ray_tpu.tune.schedulers import PB2

    pb2 = PB2("score", perturbation_interval=2,
              hyperparam_bounds={"lr": [0.0, 1.0]}, seed=0)
    pb2.register("good", {"lr": 0.8})
    pb2.register("bad", {"lr": 0.1})
    for it in (1, 2):
        decisions = pb2.on_batch([
            ("good", it, {"score": 10.0 + it}),
            ("bad", it, {"score": 1.0 + 0.1 * it}),
        ])
    d = decisions["bad"]
    assert isinstance(d, dict) and d["action"] == "clone"
    assert d["source"] == "good"
    assert 0.0 <= d["config"]["lr"] <= 1.0


def test_hyperband_end_to_end(cluster):
    """Tuner + HyperBand: the aggressive bracket prunes its loser at the
    first rung (STRICTLY below max_t); the best config wins. Cohorts run
    concurrently (sync halving's requirement — see the scheduler note)."""

    def objective(config):
        for step in range(3):
            tune.report({"acc": config["q"] - 0.01 * step})

    # max_t=3, eta=3 -> brackets b0 rungs [3], b1 rungs [1, 3].
    # 4 trials deal b0={q=.2,.8}, b1={q=.4,1.0}: b1 halves at rung 1.
    tuner = tune.Tuner(
        objective,
        param_space={"q": tune.grid_search([0.2, 0.4, 0.8, 1.0])},
        tune_config=tune.TuneConfig(
            metric="acc", mode="max", num_samples=1,
            max_concurrent_trials=4,  # whole population concurrent
            scheduler=tune.HyperBandScheduler("acc", max_t=3,
                                              reduction_factor=3)))
    grid = tuner.fit()
    best = grid.get_best_result()
    assert best.config["q"] == 1.0
    # REAL rung pruning: q=0.4 (bracket 1's loser) stopped strictly
    # below max_t (stopping at max_t would satisfy a broken scheduler).
    pruned_below_max = [r for r in grid
                        if r.stopped_early and len(r.history) < 3]
    assert pruned_below_max, [len(r.history) for r in grid]
    assert any(r.config["q"] == 0.4 for r in pruned_below_max)


class _FakeOptunaTrial:
    def __init__(self, rng):
        self._rng = rng
        self.params = {}

    def suggest_categorical(self, name, cats):
        v = self._rng.choice(list(cats))
        self.params[name] = v
        return v

    def suggest_float(self, name, lo, hi, log=False):
        v = self._rng.uniform(lo, hi)
        self.params[name] = v
        return v

    def suggest_int(self, name, lo, hi):
        v = self._rng.randint(lo, hi)
        self.params[name] = v
        return v


class _FakeOptunaStudy:
    def __init__(self, direction):
        import random as _r

        self.direction = direction
        self._rng = _r.Random(0)
        self.told = []

    def ask(self):
        return _FakeOptunaTrial(self._rng)

    def tell(self, trial, value=None, state=None):
        self.told.append((trial.params, value, state))


class _FakeOptunaModule:
    """The create_study/ask/tell surface OptunaSearch drives (optuna is
    not baked into this image; the adapter contract is what matters)."""

    def __init__(self):
        self.studies = []

    def create_study(self, direction="minimize", sampler=None):
        s = _FakeOptunaStudy(direction)
        self.studies.append(s)
        return s


def test_optuna_adapter_drives_ask_tell_seam():
    from ray_tpu.tune import OptunaSearch

    fake = _FakeOptunaModule()
    searcher = OptunaSearch(optuna_module=fake)
    searcher.set_search_properties("score", "max", {
        "lr": tune.loguniform(1e-4, 1e-1),
        "units": tune.randint(8, 64),
        "act": tune.choice(["relu", "tanh"]),
        "fixed": 7,
    })
    for i in range(5):
        tid = f"t{i}"
        cfg = searcher.suggest(tid)
        assert 1e-4 <= cfg["lr"] <= 1e-1
        assert 8 <= cfg["units"] < 64
        assert cfg["act"] in ("relu", "tanh")
        assert cfg["fixed"] == 7
        searcher.on_trial_complete(tid, {"score": float(i)})
    study = fake.studies[0]
    assert study.direction == "maximize"
    assert len(study.told) == 5
    assert all(v is not None for _p, v, _s in study.told)


def test_optuna_adapter_composes_with_tuner(cluster):
    from ray_tpu.tune import OptunaSearch, TuneConfig

    fake = _FakeOptunaModule()

    def objective(config):
        tune.report({"score": -(config["x"] - 3.0) ** 2})

    grid = tune.Tuner(
        objective,
        param_space={"x": tune.uniform(-10.0, 10.0)},
        tune_config=TuneConfig(metric="score", mode="max", num_samples=6,
                               search_alg=OptunaSearch(optuna_module=fake)),
    ).fit()
    assert len(grid) == 6
    assert len(fake.studies[0].told) == 6
