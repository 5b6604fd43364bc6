"""Elastic fault-tolerance e2e: supervisor × step-checkpoints × injectors.

The contract under test (ISSUE 1 tentpole): a worker killed at step N under
the ElasticSupervisor restarts with backoff, resumes from the last
step-granular auto-checkpoint, and reaches BITWISE-identical final
parameters to an uninterrupted run — because every batch in ft_worker.py is
a pure function of the step index and restore round-trips f32 exactly.
Corrupt-checkpoint and hang (heartbeat) faults ride the same path; NaN
injection exercises the anomaly guard's skip_step policy in-process.
"""
import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu.distributed import elastic
from paddle_tpu.distributed.elastic import (HEARTBEAT_FILE_ENV,
                                            ElasticJobError,
                                            ElasticSupervisor, WorkerSpec)
from paddle_tpu.testing.faults import KILL_EXIT_CODE, FaultInjector

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "ft_worker.py")

TOTAL_STEPS = 12
SAVE_EVERY = 4


def _run_supervised(tmp, tag, faults=None, policy=None, max_restarts=3,
                    heartbeat_timeout=None, total=TOTAL_STEPS):
    """Run one supervised ft_worker job; returns (result dict, report)."""
    ckpt = os.path.join(str(tmp), f"{tag}_ckpt")
    out = os.path.join(str(tmp), f"{tag}_out.json")
    state = os.path.join(str(tmp), f"{tag}_faults")
    env = {
        "FT_CKPT_DIR": ckpt,
        "FT_OUT": out,
        "FT_TOTAL_STEPS": str(total),
        "FT_SAVE_EVERY": str(SAVE_EVERY),
        "JAX_PLATFORMS": "cpu",
        # single host device: workers don't need the test mesh
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    if faults:
        env["PADDLE_TPU_FAULTS"] = faults
        env["PADDLE_TPU_FAULT_STATE_DIR"] = state
    if policy:
        env["FT_ANOMALY_POLICY"] = policy
    os.makedirs(ckpt, exist_ok=True)
    sup = ElasticSupervisor(max_restarts=max_restarts, backoff_base=0.05,
                            backoff_max=0.2, jitter=0.1,
                            heartbeat_timeout=heartbeat_timeout,
                            monitor_interval=0.02,
                            heartbeat_dir=os.path.join(str(tmp),
                                                       f"{tag}_hb"),
                            seed=0)
    log = os.path.join(str(tmp), f"{tag}.log")
    report = sup.run([WorkerSpec([sys.executable, WORKER], env=env,
                                 log_path=log)])
    assert os.path.exists(out), _tail(log)
    with open(out) as f:
        return json.load(f), report


def _tail(log, n=2000):
    try:
        with open(log) as f:
            return f.read()[-n:]
    except OSError:
        return "<no worker log>"


def _params(result):
    return {k: np.asarray(v, dtype=np.float32)
            for k, v in result["params"].items()}


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """One uninterrupted supervised run; every fault scenario must land on
    exactly these parameters."""
    tmp = tmp_path_factory.mktemp("ft_baseline")
    result, report = _run_supervised(tmp, "base")
    assert report["restarts"] == {0: 0}
    assert result["steps_run"] == TOTAL_STEPS
    return _params(result)


def test_kill_restart_resumes_bitwise_identical(tmp_path, baseline):
    """Acceptance: kill at step 6 (mid-interval, checkpoint at step 3) →
    supervisor restarts → worker resumes from step_3 and replays — final
    params bitwise equal to the uninterrupted run."""
    result, report = _run_supervised(tmp_path, "kill", faults="kill@6")
    assert report["restarts"] == {0: 1}
    assert report["history"][0][0][1] == f"exit code {KILL_EXIT_CODE}"
    # resumed from the step_3 snapshot, not epoch 0: first step of the
    # second incarnation is 4
    assert result["restart_count"] == 1
    assert result["first_step"] == SAVE_EVERY
    got = _params(result)
    assert sorted(got) == sorted(baseline)
    for k in baseline:
        np.testing.assert_array_equal(got[k], baseline[k], err_msg=k)


def test_corrupt_checkpoint_quarantine_end_to_end(tmp_path, baseline):
    """Acceptance: the crash also tears the newest snapshot (corrupt@9 then
    kill@9 — at step 9 the newest landed snapshot is step_7's).
    restore_latest must quarantine the torn step_7, fall back to the
    next-newest (step_3), and the job still completes and converges
    bitwise."""
    result, report = _run_supervised(tmp_path, "corrupt",
                                     faults="corrupt@9,kill@9")
    assert report["restarts"] == {0: 1}
    # the torn snapshot was quarantined, not retried forever
    assert result["quarantined"], "no .corrupt quarantine dir produced"
    # fell back to an OLDER snapshot: step_7 was torn, so resume re-ran
    # from step 4 (the step_3 snapshot)
    assert result["first_step"] == SAVE_EVERY
    got = _params(result)
    for k in baseline:
        np.testing.assert_array_equal(got[k], baseline[k], err_msg=k)


def test_nan_skip_step_counted_not_fatal(tmp_path, baseline):
    """Acceptance: NaN loss at step 5 under policy='skip_step' is dropped
    and counted; training completes without restarts and the final params
    differ from baseline ONLY by the missing step's update (sanity: all
    finite, job ran all steps)."""
    result, report = _run_supervised(tmp_path, "nan", faults="nan@5",
                                     policy="skip_step")
    assert report["restarts"] == {0: 0}
    assert result["steps_run"] == TOTAL_STEPS
    assert result["anomaly"]["skipped_steps"] == 1
    assert result["anomaly"]["checked_steps"] == TOTAL_STEPS
    got = _params(result)
    for k in baseline:
        assert np.isfinite(got[k]).all(), f"{k} poisoned despite skip_step"


def test_nan_raise_policy_exhausts_restart_budget(tmp_path):
    """Under policy='raise' a deterministic NaN is NOT transient: every
    incarnation re-fires it (fresh fault state per attempt would be a
    different scenario — here the marker fires once, but the raise happens
    before any checkpoint at step 1, so the retry replays it... therefore
    the supervisor must eventually surface ElasticJobError)."""
    ckpt = os.path.join(str(tmp_path), "raise_ckpt")
    out = os.path.join(str(tmp_path), "raise_out.json")
    env = {
        "FT_CKPT_DIR": ckpt, "FT_OUT": out,
        "FT_TOTAL_STEPS": "6", "FT_SAVE_EVERY": "100",
        "FT_ANOMALY_POLICY": "raise",
        "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
        "PADDLE_TPU_FAULTS": "nan@1",
        # no PADDLE_TPU_FAULT_STATE_DIR: untracked mode re-fires every
        # incarnation, modelling a PERSISTENT data-poisoning fault
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    os.makedirs(ckpt, exist_ok=True)
    sup = ElasticSupervisor(max_restarts=1, backoff_base=0.05,
                            backoff_max=0.1, monitor_interval=0.02,
                            heartbeat_dir=os.path.join(str(tmp_path), "hb"),
                            seed=0)
    with pytest.raises(ElasticJobError) as ei:
        sup.run([WorkerSpec([sys.executable, WORKER], env=env)])
    assert len(ei.value.history) == 2  # initial attempt + 1 restart


def test_hang_detected_by_heartbeat_and_restarted(tmp_path, baseline):
    """A worker stalled at step 8 stops beating; the supervisor kills and
    restarts it, and the resumed run still converges bitwise."""
    result, report = _run_supervised(tmp_path, "stall",
                                     faults="stall@8:3600",
                                     heartbeat_timeout=1.5)
    assert report["restarts"] == {0: 1}
    assert "hang" in report["history"][0][0][1]
    got = _params(result)
    for k in baseline:
        np.testing.assert_array_equal(got[k], baseline[k], err_msg=k)


def _slow_first_step_worker(ckpt_dir, stall_s):
    """Spawn-style worker whose first step outlasts the heartbeat timeout,
    as a first step's compile may on a loaded host; later steps are
    quick."""
    import time
    from paddle_tpu.incubate.checkpoint import AutoCheckpointManager
    acp = AutoCheckpointManager(ckpt_dir, save_every_n_steps=3)
    for step in acp.train_step_range(3):
        if step == 0:
            time.sleep(stall_s)


def test_slow_first_step_is_start_up_not_a_hang(tmp_path):
    """A beat marks a finished step, so a first step that runs for twice
    `heartbeat_timeout` is start-up the supervisor does not judge: the
    worker ends with no restart (with a beat before each step it would be
    killed as hung in every incarnation)."""
    # the hang test's timeout: a worker's exit after its last beat takes
    # 0.3-0.7 s on an 8-core CPU host under load, well inside it
    timeout = 1.5
    sup = ElasticSupervisor(max_restarts=0, heartbeat_timeout=timeout,
                            monitor_interval=0.02,
                            heartbeat_dir=str(tmp_path / "hb"), seed=0)
    report = sup.run([WorkerSpec(_slow_first_step_worker,
                                 args=(str(tmp_path / "ckpt"), 2 * timeout),
                                 env={"JAX_PLATFORMS": "cpu"})])
    assert report["restarts"] == {0: 0}
    assert report["history"] == {0: []}


# ---------------------------------------------------------------- unit level
def test_backoff_is_capped_exponential_with_jitter():
    sup = ElasticSupervisor(backoff_base=0.5, backoff_factor=2.0,
                            backoff_max=3.0, jitter=0.25, seed=42)
    d0, d1, d2, d5 = (sup.backoff_delay(n) for n in (0, 1, 2, 5))
    assert 0.5 <= d0 <= 0.5 * 1.25
    assert 1.0 <= d1 <= 1.0 * 1.25
    assert 2.0 <= d2 <= 2.0 * 1.25
    assert 3.0 <= d5 <= 3.0 * 1.25  # capped at backoff_max before jitter
    # jitter decorrelates identically-configured supervisors
    other = ElasticSupervisor(backoff_base=0.5, backoff_factor=2.0,
                              backoff_max=3.0, jitter=0.25, seed=7)
    assert any(abs(sup.backoff_delay(n) - other.backoff_delay(n)) > 1e-9
               for n in range(4))


def test_fault_injector_fire_once_markers(tmp_path):
    inj = FaultInjector("nan@3", state_dir=str(tmp_path))
    assert inj.enabled
    x = 2.0
    assert np.isnan(inj.poison_loss(3, x))
    assert inj.fired("nan", 3)
    assert inj.poison_loss(3, x) == x  # second incarnation: already fired
    assert inj.poison_loss(2, x) == x  # other steps untouched
    inert = FaultInjector("", state_dir=None)
    assert not inert.enabled
    assert inert.poison_loss(3, x) == x


def test_fault_injector_rejects_bad_spec():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultInjector("explode@3")


def test_launch_elastic_env_carries_global_rank(monkeypatch):
    """REVIEW high: the elastic launch branch must put the globally
    numbered PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM into spec.env —
    otherwise the supervisor defaults them to the local spec index and
    gang size, and a multi-node launch silently degenerates into
    independent per-node jobs."""
    import importlib
    # the package re-exports the launch() function under the same name, so
    # attribute access yields the function — import the module explicitly
    launch_mod = importlib.import_module("paddle_tpu.distributed.launch")

    captured = {}

    def fake_run(self, specs):
        captured["specs"] = specs
        return {}

    monkeypatch.setattr(ElasticSupervisor, "run", fake_run)
    monkeypatch.setattr(sys, "argv", [
        "launch", "--ips", "10.0.0.1,10.0.0.2", "--nproc_per_node", "2",
        "--node_rank", "1", "--max_restarts", "2", "train.py"])
    launch_mod.launch()

    specs = captured["specs"]
    assert [s.env["PADDLE_TRAINER_ID"] for s in specs] == ["2", "3"]
    assert [s.env["PADDLE_TRAINERS_NUM"] for s in specs] == ["4", "4"]
    # rank/endpoint consistency: the endpoint indexed by the global rank
    assert [s.env["PADDLE_CURRENT_ENDPOINT"] for s in specs] == \
        ["10.0.0.2:6170", "10.0.0.2:6171"]


def test_epoch_range_ignores_newer_step_snapshot(tmp_path):
    """REVIEW medium: restore_latest returns the newest snapshot of EITHER
    kind; train_epoch_range must not read a step snapshot's index as an
    epoch (which would silently skip up to that many epochs)."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.incubate.checkpoint import AutoCheckpointManager

    with paddle.utils.unique_name.guard():
        paddle.seed(3)
        m = paddle.nn.Linear(4, 2)
        o = opt.SGD(0.1, parameters=m.parameters())
    d = str(tmp_path / "mixed")
    acp = AutoCheckpointManager(d, models=[m], optimizers=[o],
                                save_every_n_steps=2)
    for _ in acp.train_step_range(6):
        pass  # leaves step snapshots, newest step_5

    acp2 = AutoCheckpointManager(d, models=[m], optimizers=[o])
    epochs = list(acp2.train_epoch_range(3))
    assert acp2.restored_kind == "step"  # step_5 WAS the newest snapshot
    assert epochs == [0, 1, 2]  # ...but must not fast-forward the epochs


def test_step_range_resumes_in_process(tmp_path):
    """train_step_range unit check (no subprocess): a run broken at step 6
    resumes at the step after its last step-granular snapshot."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.incubate.checkpoint import AutoCheckpointManager

    def build():
        with paddle.utils.unique_name.guard():
            paddle.seed(3)
            m = paddle.nn.Linear(4, 2)
            o = opt.SGD(0.1, parameters=m.parameters())
        return m, o

    def one(m, o, step):
        rs = np.random.RandomState(step)
        X = rs.randn(4, 4).astype("float32")
        loss = (m(paddle.to_tensor(X)) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()

    d = str(tmp_path / "steps")
    m1, o1 = build()
    acp1 = AutoCheckpointManager(d, models=[m1], optimizers=[o1],
                                 save_every_n_steps=3)
    for step in acp1.train_step_range(10):
        one(m1, o1, step)
        if step == 6:
            break  # "crash": step-5 snapshot is the last durable one

    m2, o2 = build()
    acp2 = AutoCheckpointManager(d, models=[m2], optimizers=[o2],
                                 save_every_n_steps=3)
    seen = []
    for step in acp2.train_step_range(10):
        seen.append(step)
        one(m2, o2, step)
    assert seen[0] == 6  # resumed from step_5, replaying the lost step 6
    assert acp2.restored_kind == "step" and acp2.restored_index == 5

    # uninterrupted reference
    m3, o3 = build()
    for step in range(10):
        one(m3, o3, step)
    np.testing.assert_array_equal(m2.weight.numpy(), m3.weight.numpy())


@pytest.mark.parametrize("kind,interval", [
    ("step", "save_every_n_steps"), ("epoch", "save_interval_epochs")])
def test_range_beats_after_each_step(tmp_path, monkeypatch, kind, interval):
    """The step and epoch ranges beat once each step (and its snapshot) has
    ended, never before a step starts: no beat while step 0 runs. Each
    beat records the snapshots on disk at that moment (a count that needs
    no clock)."""
    from paddle_tpu.incubate.checkpoint import AutoCheckpointManager
    hb = tmp_path / "hb"
    monkeypatch.setenv(HEARTBEAT_FILE_ENV, str(hb))
    save_dir = tmp_path / "ckpt"
    beats = []
    real = elastic.heartbeat

    def counted():
        real()
        beats.append(sorted(os.listdir(save_dir)))

    monkeypatch.setattr(elastic, "heartbeat", counted)
    acp = AutoCheckpointManager(str(save_dir), **{interval: 2})
    it = getattr(acp, f"train_{kind}_range")(3)
    assert next(it) == 0
    assert not hb.exists() and beats == []  # step 0 in progress
    assert next(it) == 1
    assert hb.exists() and beats == [[]]  # step 0 ended, nothing saved
    assert next(it) == 2
    assert beats[1] == [f"{kind}_1"]  # step 1's beat follows its snapshot
    assert list(it) == []
    assert beats[2] == [f"{kind}_1", f"{kind}_2"]
    assert len(beats) == 3
