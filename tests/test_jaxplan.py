"""Tier-1 tests for the jaxplan static planner + committed-plan gate.

Five layers:

  1. policy vocabulary — remat_group_size / candidate_policies and the
     tolerance-aware selection rule on synthetic candidate tables;
  2. envelope sweep    — on an activation-dominated tiny GPT the
     planner escalates none -> group:2 -> full as the HBM envelope
     shrinks, and raises InfeasibleEnvelope (with the byte shortfall)
     when even per-block remat does not fit;
  3. training parity   — use_recompute="auto" resolves through the
     committed plan and trains bitwise-equal to the unremat baseline;
     rematted policies match the baseline bitwise on the first loss
     (same forward) and closely thereafter;
  4. admission pricing — the quadratic prefill cost model charges a
     long prompt super-linearly, the scheduler admits against the
     FLOPs budget FCFS, and a missing model reproduces the flat path;
  5. plan gate         — tools/jaxplan.py --plan check exits 0 on the
     committed jaxplan.json, 1 on drift, 2 on usage errors; drift
     *detection* is pinned in-process via diff_plans on synthetic
     payloads (no re-trace).
"""
import copy
import functools
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.analysis import jaxplan
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.parallel import set_global_mesh

pytestmark = pytest.mark.lint


@pytest.fixture(autouse=True)
def _clear_mesh():
    """A stale global mesh (test_hlo_strategies runs right before this
    file and leaks one) flips plain TrainStep compiles into SPMD
    partitioning, which CHECK-aborts XLA — same hygiene as test_moe."""
    set_global_mesh(None)
    yield
    set_global_mesh(None)

REPO = pathlib.Path(__file__).resolve().parent.parent
JAXPLAN_CLI = REPO / "tools" / "jaxplan.py"
PLAN_FILE = REPO / "jaxplan.json"


# ------------------------------------------------------ policy vocabulary
def test_remat_group_size_vocabulary():
    assert jaxplan.remat_group_size("none", 4) == 0
    assert jaxplan.remat_group_size("", 4) == 0
    assert jaxplan.remat_group_size("full", 4) == 1
    assert jaxplan.remat_group_size("group:2", 4) == 2
    assert jaxplan.remat_group_size("group:8", 4) == 4   # clamps
    with pytest.raises(ValueError):
        jaxplan.remat_group_size("group:0", 4)
    with pytest.raises(ValueError):
        jaxplan.remat_group_size("sometimes", 4)


def test_candidate_policies_escalation_order():
    assert jaxplan.candidate_policies(2) == ["none", "group:2", "full"]
    assert jaxplan.candidate_policies(4) == \
        ["none", "group:4", "group:2", "full"]
    # non-divisors are skipped; order is always escalating
    assert jaxplan.candidate_policies(6) == \
        ["none", "group:6", "group:3", "group:2", "full"]


def _cand(policy, group, flops, peak):
    return jaxplan.RematCandidate(policy=policy, group_size=group,
                                  flops=flops, peak_bytes=peak)


def test_selection_prefers_least_aggressive_within_tolerance():
    """FLOP deltas inside the model's tolerance are noise: the planner
    must not escalate to 'full' over a sub-tolerance win."""
    cands = [_cand("none", 0, 100, 1000),
             _cand("group:2", 2, 153, 600),
             _cand("full", 1, 150, 300)]
    pick = lambda env: jaxplan.plan_remat(  # noqa: E731
        env, candidates=cands).policy
    assert pick(1000) == "none"
    assert pick(999) == "group:2"     # 153 within 5% of 150
    assert pick(599) == "full"
    with pytest.raises(jaxplan.InfeasibleEnvelope):
        pick(299)


def test_selection_escalates_past_tolerance():
    """A beyond-tolerance FLOP gap DOES pick the cheaper candidate."""
    cands = [_cand("group:2", 2, 200, 600), _cand("full", 1, 150, 300)]
    assert jaxplan.plan_remat(600, candidates=cands).policy == "full"


# --------------------------------------------------------- envelope sweep
def _sweep_builder(policy):
    """4-layer GPT at seq 64 / batch 4: activations dominate weights,
    so remat policies genuinely trade peak bytes for recompute FLOPs
    (the registry tiny GPT at seq 4 is weight-dominated and useless for
    a sweep)."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=61, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=64, use_recompute=policy)
    model = GPT(cfg)

    def loss_fn(m, x, y):
        logits = m(x)
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), y.reshape([-1]))

    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(np.ones((4, 64), np.int64))
    y = paddle.to_tensor(np.ones((4, 64), np.int64))
    return step, (x, y), cfg.num_layers


@functools.lru_cache(maxsize=1)
def _sweep_plan():
    return jaxplan.plan_remat(build=_sweep_builder)


def test_envelope_sweep_escalates_none_grouped_full():
    plan = _sweep_plan()
    by = {c.policy: c for c in plan.candidates}
    assert set(by) == {"none", "group:4", "group:2", "full"}

    # remat trades peak for FLOPs: every remat candidate recomputes
    none, g2, full = by["none"], by["group:2"], by["full"]
    assert none.peak_bytes > g2.peak_bytes > full.peak_bytes
    assert min(g2.flops, full.flops) > none.flops

    # the default envelope (15.75G) is vast: no remat
    assert plan.policy == "none"
    assert plan.recompute_flops == 0

    replan = lambda env: jaxplan.plan_remat(  # noqa: E731
        env, candidates=plan.candidates)
    # one byte under the unremat peak forces the first escalation
    p = replan(none.peak_bytes - 1)
    assert p.policy == "group:2"
    assert p.predicted_peak_bytes == g2.peak_bytes
    assert p.recompute_flops == g2.flops - none.flops > 0
    # under the grouped peak only per-block remat fits
    assert replan(g2.peak_bytes - 1).policy == "full"


def test_infeasible_envelope_raises_with_shortfall():
    plan = _sweep_plan()
    best = min(c.peak_bytes for c in plan.candidates)
    with pytest.raises(jaxplan.InfeasibleEnvelope) as ei:
        jaxplan.plan_remat(best - 1, candidates=plan.candidates)
    e = ei.value
    assert e.shortfall_bytes == 1
    assert e.best_policy == "full"
    assert f"{e.best_peak_bytes:,}" in str(e)
    assert "1 bytes short" in str(e)


# -------------------------------------------------------- training parity
def _train_losses(policy, steps=3):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=16, use_recompute=policy)
    m = GPT(cfg)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())

    def loss_fn(mm, x, y):
        logits = mm(x)
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]), y.reshape([-1]))

    step = paddle.jit.TrainStep(m, loss_fn, opt)
    x = paddle.to_tensor(np.arange(8, dtype=np.int64)[None, :] % 61)
    y = paddle.to_tensor((np.arange(8, dtype=np.int64)[None, :] + 1) % 61)
    losses = [np.asarray(step(x, y).numpy()).item() for _ in range(steps)]
    params = {k: np.asarray(p.numpy())
              for k, p in m.named_parameters()}
    return losses, params


def test_auto_trains_bitwise_equal_to_unremat_baseline():
    """The committed plan picks 'none' under the default envelope, so
    use_recompute='auto' must be the EXACT same program as no remat —
    losses and every parameter bitwise equal over multiple steps."""
    assert jaxplan.committed_remat_policy() == "none"
    base_losses, base_params = _train_losses(False)
    auto_losses, auto_params = _train_losses("auto")
    assert auto_losses == base_losses
    assert base_params.keys() == auto_params.keys()
    for k in base_params:
        assert np.array_equal(base_params[k], auto_params[k]), k


def test_rematted_policies_share_the_forward():
    """Remat changes residual storage, not forward math: the first loss
    (pre-update) is bitwise identical; later steps track closely (the
    recomputed backward may reassociate reductions)."""
    base_losses, _ = _train_losses(False)
    for pol in ("full", "group:2"):
        losses, _ = _train_losses(pol)
        assert losses[0] == base_losses[0], pol
        np.testing.assert_allclose(losses, base_losses, rtol=1e-5,
                                   err_msg=pol)


# ------------------------------------------------------- admission pricing
def test_prefill_cost_model_quadratic_pricing():
    m = jaxplan.PrefillCostModel(base_flops=10.0, flops_per_token=2.0,
                                 flops_per_token_sq=0.5)
    assert m.cost(0) == 10.0
    assert m.cost(4) == 10.0 + 8.0 + 8.0
    assert m.budget(4) == m.cost(4)
    # round-trips through the plan-file dict shape
    assert jaxplan.PrefillCostModel.from_dict(m.as_dict()) == m


def test_committed_admission_model_charges_long_prompts_superlinearly():
    """The regression the flat budget could never express: one 8k
    prompt costs far more than thirty-two 256-token prompts (same
    total tokens), because attention is quadratic in prompt length."""
    m = jaxplan.default_admission_model()
    assert m is not None, "jaxplan.json must carry an admission model"
    assert m.flops_per_token_sq > 0
    assert m.cost(8192) > 32 * m.cost(256)
    # per-token price grows with prompt length
    assert m.cost(8192) / 8192 > m.cost(256) / 256


def _scheduler(cost_model, max_prefill_tokens, max_num_seqs=16):
    from paddle_tpu.inference.serving.paged_cache import PagedKVCache
    from paddle_tpu.inference.serving.scheduler import (
        Scheduler, SchedulerConfig)
    cache = PagedKVCache(1, (1, 4), 256, 4)
    return Scheduler(
        SchedulerConfig(max_num_seqs=max_num_seqs,
                        max_prefill_tokens=max_prefill_tokens,
                        prefill_cost_model=cost_model), cache)


def _request(rid, n_tokens):
    from paddle_tpu.inference.serving.scheduler import (
        Request, SamplingParams)
    return Request(request_id=rid, prompt_ids=list(range(n_tokens)),
                   params=SamplingParams(max_tokens=4))


def test_cost_admission_budget_exhaustion_preserves_fcfs_order():
    """When the FLOPs budget runs out mid-queue the scheduler stops —
    it never skips an expensive head to admit a cheaper later request
    (FCFS, no starvation by reordering)."""
    m = jaxplan.PrefillCostModel(base_flops=0.0, flops_per_token=1.0,
                                 flops_per_token_sq=0.5)
    sch = _scheduler(m, max_prefill_tokens=16)   # budget = cost(16) = 144
    for rid in ("r0", "r1", "r2", "r3"):
        sch.add(_request(rid, 8))                # cost(8) = 40 each
    batch = sch.schedule()
    # 3 x 40 = 120 fits the 144 budget; r3's 40 > the remaining 24
    assert [r.request_id for r in batch.prefill] == ["r0", "r1", "r2"]
    assert [r.request_id for r in sch.waiting] == ["r3"]
    # r3 admits on the next step
    assert [r.request_id for r in sch.schedule().prefill] == ["r3"]


def test_cost_admission_stops_behind_expensive_head():
    """A too-expensive head blocks the line (budget spent), even though
    a later short request alone would fit."""
    m = jaxplan.PrefillCostModel(base_flops=0.0, flops_per_token=1.0,
                                 flops_per_token_sq=0.5)
    sch = _scheduler(m, max_prefill_tokens=16)   # budget = 144
    sch.add(_request("big0", 12))                # cost = 84
    sch.add(_request("big1", 12))                # 168 total: overflows
    sch.add(_request("tiny", 2))                 # would fit; behind big1
    batch = sch.schedule()
    assert [r.request_id for r in batch.prefill] == ["big0"]
    assert [r.request_id for r in sch.waiting] == ["big1", "tiny"]


def test_cost_admission_head_of_line_overflow_still_admits():
    """An untouched budget admits even a super-budget request — one
    maximal prompt must not starve (same head-of-line rule as the flat
    path)."""
    m = jaxplan.PrefillCostModel(base_flops=0.0, flops_per_token=1.0,
                                 flops_per_token_sq=0.5)
    sch = _scheduler(m, max_prefill_tokens=4)    # budget = cost(4) = 12
    sch.add(_request("huge", 40))                # cost = 840 >> 12
    batch = sch.schedule()
    assert [r.request_id for r in batch.prefill] == ["huge"]


def test_cost_admission_packs_more_short_prompts_than_flat():
    """The point of pricing: short prompts carry no quadratic term, so
    the FLOPs budget admits MORE of them per step than the flat token
    budget — capacity freed by charging long prompts their true cost."""
    quad = jaxplan.PrefillCostModel(base_flops=0.0, flops_per_token=1.0,
                                    flops_per_token_sq=1.0)
    flat_sch = _scheduler(None, max_prefill_tokens=32)
    cost_sch = _scheduler(quad, max_prefill_tokens=32)
    for sch in (flat_sch, cost_sch):
        for i in range(12):
            sch.add(_request(f"r{i}", 4))
    flat_n = len(flat_sch.schedule().prefill)    # 32 tokens -> 8 reqs
    cost_n = len(cost_sch.schedule().prefill)
    assert flat_n == 8
    # budget = 32 + 1024; cost(4) = 20 -> 12 of 12 admitted
    assert cost_n == 12 > flat_n


def test_no_cost_model_reproduces_flat_token_budget():
    sch = _scheduler(None, max_prefill_tokens=16)
    for i in range(3):
        sch.add(_request(f"r{i}", 8))
    assert [r.request_id for r in sch.schedule().prefill] == ["r0", "r1"]


# --------------------------------------------------------------- plan gate
def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(JAXPLAN_CLI), *args],
        capture_output=True, text=True, cwd=str(REPO), env=env,
        timeout=600)


def test_plan_check_passes_on_committed_file():
    """THE gate: re-planning under the committed envelope reproduces
    jaxplan.json. Drift here means a model/analyzer change silently
    altered planned policy — re-baseline with --plan write."""
    assert PLAN_FILE.exists()
    p = _cli("--plan", "check", "--format", "json")
    assert p.returncode == 0, p.stdout + p.stderr
    assert json.loads(p.stdout)["plan_violations"] == []


def test_plan_check_fails_fast_on_version_drift(tmp_path):
    committed = json.loads(PLAN_FILE.read_text())
    committed["version"] = 999
    f = tmp_path / "jaxplan.json"
    f.write_text(json.dumps(committed))
    p = _cli("--plan", "check", "--plan-file", str(f))
    assert p.returncode == 1
    assert "PLAN VIOLATION" in p.stdout and "999" in p.stdout


def test_plan_check_usage_error_exits_two():
    p = _cli("--plan", "check", "--envelope-gb", "2")
    assert p.returncode == 2
    assert "envelope" in p.stderr


def test_diff_plans_flags_structural_and_numeric_drift():
    """Drift detection pinned without re-tracing: policy flips and
    donation edits are exact-match failures; numeric drift respects
    the committed tolerance."""
    committed = json.loads(PLAN_FILE.read_text())
    assert jaxplan.diff_plans(committed, committed) == []

    # chosen-policy flip: structural, always fails
    cur = copy.deepcopy(committed)
    cur["remat"]["train_step"]["policy"] = "full"
    cur["remat"]["train_step"]["group_size"] = 1
    v = jaxplan.diff_plans(committed, cur)
    assert any("policy drifted" in s for s in v)

    # numeric drift: 4% rides, 6% fails (tolerance 5%)
    peak = committed["remat"]["train_step"]["predicted_peak_bytes"]
    cur = copy.deepcopy(committed)
    cur["remat"]["train_step"]["predicted_peak_bytes"] = int(peak * 1.04)
    assert not any("predicted_peak_bytes" in s
                   for s in jaxplan.diff_plans(committed, cur))
    cur["remat"]["train_step"]["predicted_peak_bytes"] = int(peak * 1.06)
    assert any("predicted_peak_bytes" in s
               for s in jaxplan.diff_plans(committed, cur))

    # donation set edit: exact-match failure
    cur = copy.deepcopy(committed)
    cur["donation"]["train_step"]["donate_argnums"] = [0, 2, 3]
    assert any("donate_argnums" in s
               for s in jaxplan.diff_plans(committed, cur))

    # dropped suppression: exact-match failure
    cur = copy.deepcopy(committed)
    cur["donation"]["serving.paged_decode"]["suppressed"] = {}
    assert any("suppressed" in s
               for s in jaxplan.diff_plans(committed, cur))


def test_plan_consumers_read_the_committed_file():
    """The three consumption paths resolve to what jaxplan.json says."""
    plan = json.loads(PLAN_FILE.read_text())
    assert plan["version"] == jaxplan.PLAN_VERSION
    assert jaxplan.committed_remat_policy() == \
        plan["remat"]["train_step"]["policy"]
    assert list(jaxplan.planned_donation("train_step")) == \
        plan["donation"]["train_step"]["donate_argnums"] == [0, 2, 3, 6]
    m = jaxplan.default_admission_model()
    assert m.as_dict() == plan["admission"]["prefill_cost_model"]


def test_trainstep_donation_comes_from_the_plan():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=16)
    m = GPT(cfg)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())

    def loss_fn(mm, x, y):
        return F.cross_entropy(mm(x).reshape([-1, 61]), y.reshape([-1]))

    step = paddle.jit.TrainStep(m, loss_fn, opt)
    assert step._donate_argnums == tuple(
        jaxplan.planned_donation("train_step", default=(0, 2, 3, 6)))
    undonated = paddle.jit.TrainStep(m, loss_fn, opt, donate=False)
    assert undonated._donate_argnums == ()
