"""SimClock, Actor, backoff/debounce/throttle/step-detector tests
(reference behavior: openr/common/tests/*)."""

import asyncio
import os

from openr_tpu.common.runtime import Actor, CounterMap, SimClock
from openr_tpu.common.utils import (
    AsyncDebounce,
    AsyncThrottle,
    ExponentialBackoff,
    StepDetector,
)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_simclock_orders_sleepers():
    async def main():
        clock = SimClock()
        order = []

        async def sleeper(tag, dt):
            await clock.sleep(dt)
            order.append((tag, clock.now()))

        t1 = asyncio.ensure_future(sleeper("b", 2.0))
        t2 = asyncio.ensure_future(sleeper("a", 1.0))
        await clock.run_for(3.0)
        assert order == [("a", 1.0), ("b", 2.0)]
        assert clock.now() == 3.0
        await t1
        await t2

    run(main())


def test_simclock_chained_sleeps():
    async def main():
        clock = SimClock()
        ticks = []

        async def ticker():
            for _ in range(5):
                await clock.sleep(1.0)
                ticks.append(clock.now())

        t = asyncio.ensure_future(ticker())
        await clock.run_for(10.0)
        assert ticks == [1.0, 2.0, 3.0, 4.0, 5.0]
        await t

    run(main())


def test_actor_schedule_and_stop():
    async def main():
        clock = SimClock()
        a = Actor("mod", clock)
        fired = []
        a.schedule(5.0, lambda: fired.append(clock.now()))
        await clock.run_for(4.0)
        assert fired == []
        await clock.run_for(2.0)
        assert fired == [5.0]
        await a.stop()

    run(main())


def test_exponential_backoff_doubles_and_resets():
    clock = SimClock()
    b = ExponentialBackoff(0.064, 8.192, clock)
    assert b.can_try_now()
    b.report_error()
    assert b.get_current_backoff() == 0.064
    b.report_error()
    b.report_error()
    assert b.get_current_backoff() == 0.256
    assert not b.can_try_now()
    for _ in range(10):
        b.report_error()
    assert b.at_max_backoff()
    assert b.get_current_backoff() == 8.192
    b.report_success()
    assert b.can_try_now()
    assert b.get_current_backoff() == 0.0


def test_backoff_time_remaining_advances_with_clock():
    async def main():
        clock = SimClock()
        b = ExponentialBackoff(1.0, 8.0, clock)
        b.report_error()
        assert abs(b.time_remaining_until_retry() - 1.0) < 1e-9
        await clock.run_for(0.5)
        assert abs(b.time_remaining_until_retry() - 0.5) < 1e-9
        await clock.run_for(1.0)
        assert b.can_try_now()

    run(main())


def test_async_throttle_coalesces():
    async def main():
        clock = SimClock()
        a = Actor("m", clock)
        calls = []
        th = AsyncThrottle(a, 1.0, lambda: calls.append(clock.now()))
        th()
        th()
        th()
        assert th.is_active()
        await clock.run_for(1.5)
        assert calls == [1.0]  # three invocations -> one call
        th()
        await clock.run_for(1.5)
        assert calls == [1.0, 2.5]
        await a.stop()

    run(main())


def test_async_debounce_backs_off_and_fires_once():
    async def main():
        clock = SimClock()
        a = Actor("m", clock)
        calls = []
        db = AsyncDebounce(a, 0.010, 0.250, lambda: calls.append(clock.now()))
        # rapid-fire invocations double the hold-off: 10ms, 20ms, 40ms...
        db()
        assert db.is_scheduled()
        await clock.run_for(0.005)
        db()  # reschedules to now+20ms
        await clock.run_for(0.015)
        assert calls == []  # original 10ms deadline was superseded
        await clock.run_for(0.010)
        assert calls == [0.025]
        # after firing, backoff resets to min
        db()
        await clock.run_for(0.010)
        assert len(calls) == 2
        await a.stop()

    run(main())


def test_async_debounce_max_backoff_still_fires():
    async def main():
        clock = SimClock()
        a = Actor("m", clock)
        calls = []
        db = AsyncDebounce(a, 0.010, 0.250, lambda: calls.append(clock.now()))

        async def hammer():
            for _ in range(100):
                db()
                await clock.sleep(0.01)

        t = asyncio.ensure_future(hammer())
        await clock.run_for(2.0)
        # Max debounce is 250ms: invocations every 10ms for 1s must still
        # produce at least one call within the max window.
        assert calls and calls[0] <= 0.6
        await t
        await a.stop()

    run(main())


def test_counter_map():
    c = CounterMap()
    c.bump("decision.spf_runs")
    c.bump("decision.spf_runs", 2)
    c.set("kvstore.num_keys", 7)
    assert c.get("decision.spf_runs") == 3
    assert c.dump("decision") == {"decision.spf_runs": 3}


def test_step_detector_detects_step():
    steps = []
    sd = StepDetector(
        steps.append,
        fast_window_size=4,
        slow_window_size=16,
        lower_threshold_pct=2.0,
        upper_threshold_pct=5.0,
        abs_threshold=500.0,
    )
    for _ in range(20):
        sd.add_value(1000.0)
    assert steps == []  # stable signal -> no step
    for _ in range(30):
        sd.add_value(2000.0)
    assert steps, "large sustained change must be reported"
    assert abs(steps[0] - 2000.0) < 300


def test_step_detector_ignores_noise():
    steps = []
    sd = StepDetector(steps.append, fast_window_size=4, slow_window_size=16)
    vals = [1000, 1010, 995, 1005, 990, 1008, 1002, 997] * 8
    for v in vals:
        sd.add_value(float(v))
    assert steps == []


def test_actor_tasks_pruned_on_completion():
    async def main():
        clock = SimClock()
        a = Actor("m", clock)
        for _ in range(100):
            a.schedule(0.001, lambda: None)
        await clock.run_for(1.0)
        assert len(a._tasks) == 0  # completed timers must not accumulate
        await a.stop()

    run(main())


def test_persistent_compile_cache_placement(monkeypatch, tmp_path):
    """enable_persistent_compile_cache: a JAX_COMPILATION_CACHE_DIR set
    from outside places the cache (no directory is set in code);
    without it the fixed checkout path is used.  Idempotent."""
    import sys

    import openr_tpu.ops.platform_env as pe

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert pe.compile_cache_dir() == os.path.join(repo, ".jax_compile_cache")
    calls = []

    class FakeConfig:
        @staticmethod
        def update(k, v):
            calls.append((k, v))

    class FakeJax:
        config = FakeConfig()

    monkeypatch.setitem(sys.modules, "jax", FakeJax())

    # placed from outside: JAX reads the variable itself
    monkeypatch.setattr(pe, "_COMPILE_CACHE_ENABLED", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "ext"))
    pe.enable_persistent_compile_cache()
    assert pe._COMPILE_CACHE_ENABLED
    assert not [k for k, _v in calls if k == "jax_compilation_cache_dir"]

    # unset: the fixed <checkout>/.jax_compile_cache path
    calls.clear()
    monkeypatch.setattr(pe, "_COMPILE_CACHE_ENABLED", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(pe, "compile_cache_dir", lambda: str(tmp_path / "cc"))
    pe.enable_persistent_compile_cache()
    assert ("jax_compilation_cache_dir", str(tmp_path / "cc")) in calls
    assert (tmp_path / "cc").is_dir()
    # idempotent
    n = len(calls)
    pe.enable_persistent_compile_cache()
    assert len(calls) == n
