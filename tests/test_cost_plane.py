"""Cost plane: priced envs, spot hazards, egress-priced links, and the
price-aware horizon DP — including the degenerate-case guarantees (zero
prices must reproduce the seconds-only DP and the committed decision
goldens bit-for-bit)."""
import json
import os

import pytest

from repro.core import (
    EnvironmentRegistry, ExecutionEnvironment, HybridRuntime,
    MigrationAnalyzer, SessionScheduler, gpu_training_notebook,
    remote_sensing_notebook,
)
from repro.launch.notebook import (
    parse_egress_spec, parse_hazard_spec, parse_price_spec,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "fig_decisions_golden.json")


# -- fabric: prices, hazards, egress -----------------------------------


def test_env_price_and_hazard_tags():
    e = ExecutionEnvironment("spot", speedup=8.0, price_per_hour=0.9,
                             hazard_rate=1 / 600)
    assert e.price_per_hour == 0.9 and e.spot
    assert not ExecutionEnvironment("ondemand", price_per_hour=3.0).spot
    with pytest.raises(ValueError):
        ExecutionEnvironment("bad", price_per_hour=-1.0)
    with pytest.raises(ValueError):
        ExecutionEnvironment("bad", hazard_rate=-1.0)


def _reg(**envs):
    reg = EnvironmentRegistry(default_bandwidth=1e9, default_latency=0.1)
    reg.register(ExecutionEnvironment("local"), home=True)
    for name, kw in envs.items():
        reg.register(ExecutionEnvironment(name, **kw))
    return reg


def test_egress_pricing_is_directional():
    reg = _reg(remote={"speedup": 10.0})
    reg.set_egress("remote", "local", 90.0)
    assert reg.transfer_dollars("remote", "local", 2e9) == 180.0
    assert reg.transfer_dollars("local", "remote", 2e9) == 0.0
    assert reg.transfer_dollars("local", "local", 2e9) == 0.0


def test_asymmetric_link_via_connect_reverse_overrides():
    reg = _reg(remote={"speedup": 10.0})
    reg.connect("local", "remote", bandwidth=1e9, latency=0.1,
                egress_per_gb=0.0, reverse_bandwidth=2e8,
                reverse_egress_per_gb=0.09)
    fwd, back = reg.link("local", "remote"), reg.link("remote", "local")
    assert fwd.bandwidth == 1e9 and back.bandwidth == 2e8
    assert fwd.egress_per_gb == 0.0 and back.egress_per_gb == 0.09
    assert back.latency == fwd.latency          # falls back to forward


def test_clone_topology_carries_prices_and_hazards():
    reg = _reg(spot={"speedup": 8.0, "price_per_hour": 0.9,
                     "hazard_rate": 1 / 300})
    clone = reg.clone_topology()
    assert clone["spot"].price_per_hour == 0.9
    assert clone["spot"].hazard_rate == 1 / 300


# -- analyzer: dollar helpers and the SLO ------------------------------


def _analyzer(**kw):
    from repro.core import ContextDetector, KnowledgeBase
    reg = _reg(ondemand={"speedup": 10.0, "price_per_hour": 3.6},
               spot={"speedup": 8.0, "price_per_hour": 0.9,
                     "hazard_rate": 1 / 100})
    an = MigrationAnalyzer(KnowledgeBase(), ContextDetector(),
                           registry=reg, **kw)
    return an, reg


def test_exec_dollars_and_transfer_dollars():
    an, reg = _analyzer(objective="dollars")
    assert an.exec_dollars(3600.0, "ondemand") == pytest.approx(3.6)
    assert an.exec_dollars(3600.0, "local") == 0.0
    reg.set_egress("spot", "local", 10.0)
    assert an.transfer_dollars(1e9, "spot", "local") == pytest.approx(10.0)


def test_hazard_surcharge_scales_with_exposure():
    an, _ = _analyzer(objective="dollars")
    s1, d1 = an.hazard_surcharge("spot", 10.0, 1 << 20)
    s2, d2 = an.hazard_surcharge("spot", 20.0, 1 << 20)
    assert s2 > s1 > 0.0 and d2 >= d1 >= 0.0
    assert an.hazard_surcharge("ondemand", 20.0, 1 << 20) == (0.0, 0.0)


def test_objective_validation():
    from repro.core import ContextDetector, KnowledgeBase
    with pytest.raises(ValueError):
        MigrationAnalyzer(KnowledgeBase(), ContextDetector(),
                          objective="euros")
    with pytest.raises(ValueError):
        MigrationAnalyzer(KnowledgeBase(), ContextDetector(),
                          objective="dollars")      # needs a registry
    with pytest.raises(ValueError):
        _analyzer(objective="dollars", slo=-1.0)


def _run_gpu(objective, slo, *, prices=True):
    reg = EnvironmentRegistry(default_bandwidth=2e8, default_latency=0.3)
    reg.register(ExecutionEnvironment("local"), home=True, capacity=8)
    reg.register(ExecutionEnvironment(
        "ondemand", speedup=10.0,
        price_per_hour=3.0 if prices else 0.0), capacity=4)
    reg.register(ExecutionEnvironment(
        "cheap", speedup=8.0,
        price_per_hour=0.9 if prices else 0.0), capacity=4)
    sched = SessionScheduler(reg)
    rt = sched.add_notebook(gpu_training_notebook(f"t-{objective}"),
                            policy="horizon", use_knowledge=False,
                            objective=objective, slo=slo)
    rep = sched.run()
    return rt, rep


def test_slo_forces_training_off_home_and_dollars_picks_cheap():
    # 45 s steps breach a 30 s SLO at home; the dollars DP must leave and
    # must prefer the $0.9/h env over the $3/h one
    rt, rep = _run_gpu("dollars", 30.0)
    assert rt.exec_env_seconds.get("cheap", 0.0) > 0.0
    assert rt.exec_env_seconds.get("ondemand", 0.0) == 0.0
    assert rep.slo_attainment == 1.0
    assert rep.total_dollars > 0.0
    # seconds DP on the same fleet chases the fastest env instead
    rt2, rep2 = _run_gpu("seconds", 30.0)
    assert rt2.exec_env_seconds.get("ondemand", 0.0) > 0.0
    assert rep2.total_dollars > rep.total_dollars


def test_without_slo_dollars_dp_stays_on_free_home():
    rt, rep = _run_gpu("dollars", None)
    assert rep.total_dollars == 0.0
    assert set(e for e, s in rt.exec_env_seconds.items() if s > 0) \
        == {"local"}


# -- degenerate case: zero prices == seconds DP ------------------------


def test_zero_price_fleet_matches_seconds_dp_schedule():
    rt_d, rep_d = _run_gpu("dollars", None, prices=False)
    rt_s, rep_s = _run_gpu("seconds", None, prices=False)
    assert rep_d.makespan == rep_s.makespan
    assert rep_d.actual_env_seconds == rep_s.actual_env_seconds
    assert rt_d.exec_env_seconds == rt_s.exec_env_seconds
    assert rep_d.total_dollars == rep_s.total_dollars == 0.0


_FIG_SWEEPS = {"fig5_fig6": "fig5_fig6_policy_speedups",
               "fig11": "fig11_knowledge_policy"}


@pytest.mark.parametrize("fig", sorted(_FIG_SWEEPS))
def test_fig_decisions_match_golden(fig):
    """Zero prices, no hazards, symmetric links and no replicator attached
    (the defaults): the fig5/fig6 and fig11 decision sweeps must reproduce
    the committed goldens — neither the cost plane nor the replication hook
    may perturb a decision or a count.  Row names, details and integral
    values (decisions, flags, counts) are pinned exactly; the speedups and
    slopes are float expressions whose last bit varies with the numeric
    toolchain, so they are compared at a relative tolerance of 1e-12."""
    import importlib
    sweep = importlib.import_module(f"benchmarks.{_FIG_SWEEPS[fig]}")
    with open(GOLDEN) as f:
        golden = json.load(f)[fig]
    fresh = [list(row) for row in sweep.run(smoke=True)]
    assert [(n, d) for n, _v, d in fresh] == [(n, d) for n, _v, d in golden]
    for (name, v, _d), (_n, g, _gd) in zip(fresh, golden):
        if float(g).is_integer():
            assert v == g, name
        else:
            assert v == pytest.approx(g, rel=1e-12, abs=0.0), name


# -- spot hazards: seeded, deterministic, recoverable ------------------


def _spot_fleet(seed):
    reg = EnvironmentRegistry(default_bandwidth=2e8, default_latency=0.3)
    reg.register(ExecutionEnvironment("local"), home=True, capacity=8)
    reg.register(ExecutionEnvironment(
        "spot", speedup=8.0, price_per_hour=0.9,
        hazard_rate=1 / 30), capacity=4)
    sched = SessionScheduler(reg)
    sched.enable_recovery("checkpoint", interval=15.0)
    for i in range(2):
        sched.add_notebook(gpu_training_notebook(f"s{i}"),
                           policy="horizon", use_knowledge=False,
                           objective="dollars", slo=30.0)
    injected = sched.enable_spot_hazards(seed=seed, recover_after=10.0)
    return sched, injected


def test_spot_hazards_inject_through_failure_machinery():
    sched, injected = _spot_fleet(seed=2)
    assert injected > 0
    rep = sched.run()
    assert rep.preemptions == injected
    assert rep.recoveries > 0          # a preemption landed mid-run
    assert rep.total_dollars > 0.0


def test_seeded_spot_run_is_deterministic():
    rep_a = _spot_fleet(seed=2)[0].run()
    rep_b = _spot_fleet(seed=2)[0].run()
    assert rep_a == rep_b
    # a different seed draws different preemption times
    rep_c = _spot_fleet(seed=3)[0].run()
    assert [f for f in rep_c.failures] != [f for f in rep_a.failures]


def test_home_env_never_gets_hazard_injection():
    reg = EnvironmentRegistry()
    reg.register(ExecutionEnvironment("local", hazard_rate=0.0), home=True)
    reg.register(ExecutionEnvironment("spot", speedup=4.0,
                                      hazard_rate=1 / 10))
    sched = SessionScheduler(reg)
    sched.enable_spot_hazards(seed=0, horizon=100.0)
    assert all(env == "spot" for env, _at, _rec in sched._failures)


# -- data gravity ------------------------------------------------------


def test_dollars_dp_keeps_compute_at_the_data():
    reg = EnvironmentRegistry(default_bandwidth=2e8, default_latency=0.3)
    reg.register(ExecutionEnvironment("local"), home=True, capacity=8)
    reg.register(ExecutionEnvironment("near", speedup=6.0,
                                      price_per_hour=1.0), capacity=4)
    reg.register(ExecutionEnvironment("far", speedup=8.0,
                                      price_per_hour=3.0), capacity=4)
    for src in ("local", "near"):
        reg.set_egress(src, "far", 40.0)
        reg.set_egress("far", src, 80.0)
    sched = SessionScheduler(reg)
    rt = sched.add_notebook(remote_sensing_notebook("rs", scenes=3),
                            policy="horizon", use_knowledge=False,
                            objective="dollars", slo=12.0)
    rep = sched.run()
    assert rt.exec_env_seconds.get("near", 0.0) > 0.0
    assert rt.exec_env_seconds.get("far", 0.0) == 0.0
    assert rep.egress_dollars == 0.0
    assert rep.slo_attainment == 1.0


# -- workload factories ------------------------------------------------


def test_workload_factories_execute_end_to_end():
    for nb in (gpu_training_notebook(steps=2, step_cost=5.0),
               remote_sensing_notebook(scenes=2, band_cost=5.0)):
        reg = _reg(remote={"speedup": 10.0})
        rt = HybridRuntime(nb, registry=reg, use_knowledge=False)
        for i in range(len(nb.cells)):
            rt.run_cell(i)
        assert rt.envs[rt.analyzer.home].state  # produced real variables


# -- CLI spec parsers --------------------------------------------------


def test_parse_price_spec():
    assert parse_price_spec("remote:3.0") == ("remote", 3.0)
    for bad in ("remote", "remote:-1", "remote:x"):
        with pytest.raises(ValueError):
            parse_price_spec(bad)


def test_parse_hazard_spec_units():
    env, rate = parse_hazard_spec("spot:6/h")
    assert env == "spot" and rate == pytest.approx(6 / 3600)
    assert parse_hazard_spec("spot:0.1/s")[1] == pytest.approx(0.1)
    # bare rates default to per-hour (the billing-friendly unit)
    assert parse_hazard_spec("spot:6")[1] == pytest.approx(6 / 3600)
    for bad in ("spot", "spot:-6/h", "spot:6/d"):
        with pytest.raises(ValueError):
            parse_hazard_spec(bad)


def test_parse_egress_spec():
    assert parse_egress_spec("remote:local:0.09") \
        == ("remote", "local", 0.09)
    for bad in ("remote:0.09", "remote:local:-1", "a:b:x"):
        with pytest.raises(ValueError):
            parse_egress_spec(bad)
