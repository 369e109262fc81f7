"""Background delta replication: trickle → bank → claim/cancel lifecycle,
the unified speculation-waste ledger and liveness pruning.  The degenerate
case (replication off reproduces the golden decisions) is
``test_cost_plane.py::test_fig_decisions_match_golden``."""
import numpy as np
import pytest

from repro.core import (
    EnvironmentRegistry, ExecutionEnvironment, HybridRuntime, Notebook,
    SessionScheduler,
)
from repro.core import telemetry as T


def _replicated_runtime(think_cells=None, **kw):
    nb = Notebook("rep-demo")
    nb.add_cell("import numpy as np\n"
                "a = np.arange(4000, dtype=np.float64)\n"
                "b = np.arange(100, dtype=np.float64)", cost=0.1)
    nb.add_cell("c = float(a.sum() + b.sum())", cost=30.0)
    nb.add_cell("d = c + 1", cost=0.1)
    rt = HybridRuntime(
        nb, envs={"local": ExecutionEnvironment("local"),
                  "remote": ExecutionEnvironment("remote", speedup=10.0)},
        policy="cost", use_knowledge=False, latency=0.01, bandwidth=1e6, **kw)
    rep = rt.attach_replicator(rate=1e9, top_k=2)
    return nb, rt, rep


def test_trickle_banks_then_claim_ships_manifest_only():
    """Think-time trickle lands state in the target's bank; the decision-time
    migration claims it for manifest-sized bytes instead of re-shipping."""
    nb, rt, rep = _replicated_runtime()
    rt.run_cell(0)
    # think-time gap: the replicator wakes and trickles toward the
    # predicted next cell's environment (the heavy cell 1 -> remote)
    shipped = rep.step(rt.clock.now() + 1.0, budget_bytes=1 << 30)
    assert shipped > 0
    assert "a" in rep.banked.get("remote", {})
    banked_before = rep.banked_bytes("remote")
    assert banked_before == shipped
    rt.run_cell(1)                       # migrates local -> remote
    mig = next(m for m in rt.engine.log if m.dst == "remote" and not m.noop)
    assert set(mig.claimed) >= {"a"}     # banked names claimed, not re-sent
    assert mig.nbytes < banked_before / 10   # manifest-only residual
    assert rep.claimed_bytes > 0
    assert "remote" not in rep.banked or "a" not in rep.banked["remote"]
    assert float(rt.envs["remote"].state["c"]) == pytest.approx(
        float(np.arange(4000, dtype=np.float64).sum()
              + np.arange(100, dtype=np.float64).sum()))
    rt.close()


def test_trickle_does_not_touch_target_namespace_until_claim():
    """Banked chunks are speculative: the receiving namespace must not see
    the name before a migration claims it."""
    nb, rt, rep = _replicated_runtime()
    rt.run_cell(0)
    rep.step(rt.clock.now() + 1.0, budget_bytes=1 << 30)
    assert "a" in rep.banked.get("remote", {})
    assert "a" not in rt.envs["remote"].state.ns
    rt.close()


def test_midtrickle_redefinition_tombstones_bank_and_charges_waste():
    """A cell that redefines a banked name invalidates the banked copy
    (CANCEL) and folds the dead bytes into the one speculation-waste
    ledger — regression for stale banks surviving redefinition."""
    nb, rt, rep = _replicated_runtime()
    rt.run_cell(0)
    rep.step(rt.clock.now() + 1.0, budget_bytes=1 << 30)
    wasted_entry = rep.banked["remote"]["a"].nbytes
    assert wasted_entry > 0
    eng = rt.engine
    before = eng.prefetch_wasted_bytes
    rt.run_cell(0)                        # redefines a and b mid-trickle
    assert "a" not in rep.banked.get("remote", {})
    assert eng.prefetch_wasted_bytes >= before + wasted_entry
    assert rep.cancelled_names >= 1
    cancels = [m for m in rt.bus.messages()
               if m.type == T.STATE_TRICKLE_CANCELLED]
    assert cancels and "a" in cancels[-1].payload["names"]
    rt.close()


def test_superseded_trickle_charges_old_bytes_to_waste_ledger():
    """Re-trickling a name that is already banked replaces the entry and
    accounts the superseded bytes as waste."""
    nb, rt, rep = _replicated_runtime()
    rt.run_cell(0)
    rep.step(rt.clock.now() + 1.0, budget_bytes=1 << 30)
    old = rep.banked["remote"]["a"].nbytes
    # redefine and re-trickle: invalidate() fires first (tombstone), so to
    # exercise the supersede path, mutate the bank clock directly by
    # re-banking the same names via a fresh trickle after a no-invalidate
    # change to the dirty ledger
    rt.envs["local"].execute("a = a * 2.0")
    rt.envs["local"].state.mark_dirty(["a"])
    before = rt.engine.prefetch_wasted_bytes
    rep.step(rt.clock.now() + 2.0, budget_bytes=1 << 30)
    assert rep.banked["remote"]["a"].nbytes > 0
    assert rt.engine.prefetch_wasted_bytes >= before + old
    rt.close()


def test_liveness_prunes_dead_names_from_trickle_and_return():
    """Names no remaining cell can reach are skipped by both the trickle
    and the full-state return migration."""
    nb = Notebook("rep-dead")
    nb.add_cell("import numpy as np\n"
                "big_dead = np.arange(50000, dtype=np.float64)\n"
                "keep = np.arange(100, dtype=np.float64)", cost=0.1)
    nb.add_cell("r = float(keep.sum())", cost=30.0)
    nb.add_cell("s = r + 1", cost=0.1)
    rt = HybridRuntime(
        nb, envs={"local": ExecutionEnvironment("local"),
                  "remote": ExecutionEnvironment("remote", speedup=10.0)},
        policy="cost", use_knowledge=False, latency=0.01, bandwidth=1e6)
    rep = rt.attach_replicator(rate=1e9, liveness=True)
    rt.run_cell(0)
    remaining = [nb.cells[1].source, nb.cells[2].source]
    rep.step(rt.clock.now() + 1.0, remaining_sources=remaining,
             budget_bytes=1 << 30)
    banked = rep.banked.get("remote", {})
    assert "keep" in banked and "big_dead" not in banked
    rt.run_cell(1)
    shipped = {n for m in rt.engine.log for n in m.names}
    assert "big_dead" not in shipped
    assert float(rt.envs["remote"].state["r"]) == pytest.approx(
        float(np.arange(100, dtype=np.float64).sum()))
    rt.close()


def test_replication_events_on_bus():
    nb, rt, rep = _replicated_runtime()
    rt.run_cell(0)
    rep.step(rt.clock.now() + 1.0, budget_bytes=1 << 30)
    rt.run_cell(1)
    types = [m.type for m in rt.bus.messages()]
    assert T.STATE_TRICKLED in types
    assert T.STATE_TRICKLE_CLAIMED in types
    rt.close()


def test_recover_from_failure_forgets_banks():
    """A failed env's bank is stale by definition: recovery drops it and
    charges the bytes to the waste ledger."""
    nb, rt, rep = _replicated_runtime()
    rt.run_cell(0)
    rep.step(rt.clock.now() + 1.0, budget_bytes=1 << 30)
    wasted_entry = rep.banked_bytes("remote")
    assert wasted_entry > 0
    before = rt.engine.prefetch_wasted_bytes
    rt.recover_from_failure("remote")
    assert rep.banked_bytes("remote") == 0
    assert rt.engine.prefetch_wasted_bytes >= before + wasted_entry
    rt.close()


# -- scheduler integration --------------------------------------------


def _fleet(replicate: bool):
    reg = EnvironmentRegistry(default_bandwidth=1e6, default_latency=0.01)
    reg.register(ExecutionEnvironment("local"), home=True, capacity=4)
    reg.register(ExecutionEnvironment("remote", speedup=10.0), capacity=4)
    sched = SessionScheduler(reg)
    nb = Notebook("fleet-rep")
    nb.add_cell("import numpy as np\n"
                "v = np.arange(4000, dtype=np.float64)", cost=0.1)
    nb.add_cell("t = float(v.sum())", cost=30.0)
    nb.add_cell("u = t + 1", cost=0.1)
    sched.add_notebook(nb, plan=[0, 1, 2], policy="cost",
                       use_knowledge=False, think=[5.0, 5.0, 5.0])
    if replicate:
        sched.enable_replication(rate=1e9, interval=1.0)
    return sched


def test_scheduler_replication_report_fields():
    rep = _fleet(replicate=True).run()
    assert rep.trickled_bytes > 0
    assert rep.trickle_claimed_bytes > 0
    s = rep.sessions[0]
    assert s.trickled_bytes == rep.trickled_bytes
    assert s.trickle_claimed_bytes == rep.trickle_claimed_bytes
    assert rep.wasted_speculation_bytes >= 0


def test_scheduler_without_replication_reports_zero_trickle():
    rep = _fleet(replicate=False).run()
    assert rep.trickled_bytes == 0
    assert rep.trickle_claimed_bytes == 0


