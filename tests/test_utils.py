import numpy as np
import pytest

from scalerl_tpu.utils import (
    EpisodeMetrics,
    LinearDecayScheduler,
    MultiStepScheduler,
    PiecewiseScheduler,
    Timings,
    calculate_mean,
)
from scalerl_tpu.utils.metrics import calculate_vectorized_scores


def test_linear_decay():
    s = LinearDecayScheduler(1.0, 0.1, total_steps=9)
    assert s.value(0) == pytest.approx(1.0)
    assert s.value(9) == pytest.approx(0.1)
    assert s.value(100) == pytest.approx(0.1)
    mid = s.value(4)
    assert 0.1 < mid < 1.0


def test_piecewise():
    s = PiecewiseScheduler([(0, 1.0), (10, 0.5), (20, 0.1)])
    assert s.value(5) == 1.0
    assert s.value(10) == 0.5
    assert s.value(25) == 0.1
    with pytest.raises(ValueError):
        PiecewiseScheduler([(10, 1.0), (0, 0.5)])


def test_multistep():
    s = MultiStepScheduler(1.0, [5, 10], gamma=0.1)
    assert s.value(0) == 1.0
    assert s.value(5) == pytest.approx(0.1)
    assert s.value(10) == pytest.approx(0.01)


def test_episode_metrics():
    m = EpisodeMetrics(num_envs=2)
    m.step(np.array([1.0, 2.0]), np.array([False, False]))
    done = m.step(np.array([1.0, 2.0]), np.array([True, False]))
    assert done == 1
    assert m.episode_returns == [2.0]
    assert m.episode_lengths == [2]
    m.step(np.array([5.0, 2.0]), np.array([False, True]))
    assert m.episode_returns == [2.0, 6.0]
    s = m.summary()
    assert s["episodes"] == 2
    assert s["return_mean"] == pytest.approx(4.0)


def test_vectorized_scores():
    rewards = np.array([[1.0, 2.0], [1.0, 2.0], [1.0, 2.0]])
    dones = np.array([[False, True], [False, False], [True, True]])
    scores = calculate_vectorized_scores(rewards, dones)
    assert sorted(scores) == [2.0, 3.0, 4.0]


def test_calculate_mean():
    out = calculate_mean([{"a": 1.0, "b": 2.0}, {"a": 3.0}])
    assert out == {"a": 2.0, "b": 2.0}


def test_timings():
    t = Timings()
    t.time("a")
    t.time("b")
    assert set(t.means()) == {"a", "b"}
    assert "total" in t.summary()


def test_target_updates():
    import jax.numpy as jnp

    from scalerl_tpu.utils import hard_target_update, soft_target_update

    online = {"w": jnp.ones(3)}
    target = {"w": jnp.zeros(3)}
    new_t = soft_target_update(online, target, tau=0.1)
    np.testing.assert_allclose(np.asarray(new_t["w"]), 0.1 * np.ones(3), rtol=1e-6)
    hard = hard_target_update(online, target)
    np.testing.assert_allclose(np.asarray(hard["w"]), np.ones(3))


@pytest.mark.slow  # ~15 s profiler e2e; annotation plumbing has no tier-1-critical
# correctness surface (ISSUE 19 tier-1 budget buy-back)
def test_profiling_trace_and_span_hook(tmp_path):
    """Importing ``utils.profiling`` installs the profiler half of
    ``tracing.span``: a span opened under a capture lands in the trace
    file as a host annotation ``scalerl.<name>``."""
    import jax
    import jax.numpy as jnp

    from scalerl_tpu.runtime import tracing
    from scalerl_tpu.utils.profiling import maybe_trace, step_marker

    assert tracing.get_annotator() is jax.profiler.TraceAnnotation
    with maybe_trace(str(tmp_path / "prof")):
        with tracing.span("host_region"):
            x = jnp.ones((8, 8)) @ jnp.ones((8, 8))
        with step_marker(0):
            x = (x * 2).sum()
    assert float(x) == 1024.0
    (xplane,) = (tmp_path / "prof").rglob("*.xplane.pb")  # trace file written
    names = {
        ev.name
        for plane in jax.profiler.ProfileData.from_file(str(xplane)).planes
        for line in plane.lines
        for ev in line.events
    }
    assert "scalerl.host_region" in names
    with maybe_trace(None):  # disabled path is a clean no-op
        pass


# ---------------------------------------------------------------------------
# timers: monotonic clock + KeyError-proof stds (telemetry PR satellites)


def test_timings_stds_never_recorded_key_returns_zero():
    t = Timings()
    t.time("a")
    stds = t.stds()
    assert stds["a"] >= 0.0
    # never-recorded key: 0.0, not KeyError (summary consumers probe
    # speculative keys that only some topologies emit)
    assert stds["no_such_event"] == 0.0
    # and the probe must not grow phantom entries in the real stats
    assert set(t.means()) == {"a"}


def test_timings_single_sample_std_is_zero():
    t = Timings()
    t.time("once")
    assert t.stds()["once"] == 0.0


def test_timings_uses_monotonic_clock(monkeypatch):
    import time as _time

    from scalerl_tpu.utils import timers as timers_mod

    # a wall-clock jump must not corrupt the Welford stats: timers read
    # time.monotonic, so stepping time.time backwards changes nothing
    t = Timings()
    real_monotonic = _time.monotonic
    t.time("step")
    monkeypatch.setattr(
        timers_mod.time, "time", lambda: real_monotonic() - 3600.0, raising=False
    )
    t.time("step")
    assert all(v >= 0.0 for v in t.means().values())
    assert all(v >= 0.0 for v in t.stds().values())


def test_timer_monotonic_interval_checks():
    from scalerl_tpu.utils.timers import Timer

    with Timer() as tm:
        assert tm.since_start() >= 0.0
        assert not tm.check_time(3600.0)
        assert tm.check_time(0.0)  # zero interval always fires


# ---------------------------------------------------------------------------
# loggers: interval gating, TB resume, and the registry-backed write path


class _RecordingLogger:
    """Concrete BaseLogger capturing every gated write."""

    def __init__(self, **intervals):
        from scalerl_tpu.utils.loggers import BaseLogger

        class _L(BaseLogger):
            def __init__(inner, **kw):
                super().__init__(**kw)
                inner.writes = []

            def write(inner, step_type, step, data):
                inner.writes.append((step_type, step, dict(data)))

        self.logger = _L(**intervals)


def test_logger_interval_gating_train_and_update():
    lg = _RecordingLogger(train_interval=100, update_interval=50).logger
    lg.log_train_data({"loss": 1.0}, step=0)      # 0 - (-1) = 1 < 100: gated
    lg.log_train_data({"loss": 2.0}, step=99)     # 99 - (-1) = 100: lands
    lg.log_train_data({"loss": 3.0}, step=100)    # 100 - 99 < 100: gated
    lg.log_train_data({"loss": 4.0}, step=150)    # still gated
    lg.log_train_data({"loss": 5.0}, step=200)    # 200 - 99 >= 100: lands
    lg.log_update_data({"q": 1.0}, step=49)       # 49 - (-1) = 50: lands
    lg.log_update_data({"q": 2.0}, step=60)       # 60 - 49 < 50: gated
    lg.log_update_data({"q": 3.0}, step=80)       # still gated
    train_steps = [s for t, s, _ in lg.writes if t == "train/env_step"]
    update_steps = [s for t, s, _ in lg.writes if t == "update/gradient_step"]
    assert train_steps == [99, 200]
    assert update_steps == [49]
    # namespace prefixes applied
    assert all("train/loss" in d for t, _, d in lg.writes if t == "train/env_step")


def test_logger_registry_backed_write_path():
    from scalerl_tpu.runtime import telemetry

    telemetry.reset()
    reg = telemetry.get_registry()
    reg.gauge("train.loss").set(0.25)
    reg.gauge("train.fps").set(900.0)
    reg.counter("queue.actor_errors").inc()
    lg = _RecordingLogger(train_interval=1).logger
    lg.log_registry(10, step_type="train", include_prefixes=("train.",))
    assert len(lg.writes) == 1
    _, step, data = lg.writes[0]
    assert step == 10
    # instrument namespace folds into the gating namespace (train.loss ->
    # train/loss, not train/train/loss); excluded prefixes stay out
    assert data["train/loss"] == 0.25
    assert data["train/fps"] == 900.0
    assert not any("actor_errors" in k for k in data)
    # unknown step_type is a loud error, not a silent drop
    import pytest as _pytest

    with _pytest.raises(ValueError):
        lg.log_registry(11, step_type="bogus")
    telemetry.reset()


def test_tensorboard_logger_resume_roundtrip(tmp_path):
    pytest.importorskip("tensorboardX")
    pytest.importorskip("tensorboard")
    from scalerl_tpu.utils.loggers import TensorboardLogger

    log_dir = str(tmp_path / "tb")
    lg = TensorboardLogger(log_dir, train_interval=1, update_interval=1)
    lg.log_train_data({"loss": 1.0}, step=500)
    lg.save_data(epoch=3, env_step=500, gradient_step=42)
    lg.close()

    # a fresh logger over the same dir replays the event files
    lg2 = TensorboardLogger(log_dir, train_interval=100, update_interval=100)
    epoch, env_step, gradient_step = lg2.restore_data()
    assert (epoch, env_step, gradient_step) == (3, 500, 42)
    # gating counters restored: the next write below the restored step+interval
    # is suppressed (no rewound duplicate points in the resumed event stream)
    lg2.log_train_data({"loss": 2.0}, step=510)
    lg2.log_train_data({"loss": 2.0}, step=600)  # >= 500 + 100: lands
    lg2.close()
    assert lg2.last_log_train_step == 600


def test_tensorboard_logger_registry_write(tmp_path):
    pytest.importorskip("tensorboardX")
    pytest.importorskip("tensorboard")
    from tensorboard.backend.event_processing import event_accumulator

    from scalerl_tpu.runtime import telemetry
    from scalerl_tpu.utils.loggers import TensorboardLogger

    telemetry.reset()
    telemetry.get_registry().gauge("train.fps").set(1234.0)
    log_dir = str(tmp_path / "tb")
    lg = TensorboardLogger(log_dir, train_interval=1)
    lg.log_registry(7, step_type="train", include_prefixes=("train.",))
    lg.close()
    ea = event_accumulator.EventAccumulator(log_dir)
    ea.Reload()
    scalars = ea.Scalars("train/fps")
    assert scalars and scalars[-1].value == pytest.approx(1234.0)
    assert scalars[-1].step == 7
    telemetry.reset()
