"""hv_sched (paper §4.3, Fig 9 / Fig 14b): shares, penalties, hotplug."""
import time

from repro.core.config import SchedulerConfig, small_test_config
from repro.core.scheduler import BACK, FRONT, HvScheduler


def spin_task(duration, granted=None, key=None):
    """Spin for the granted quantum (at most ``duration``); with
    ``granted``, add each slice the scheduler granted to ``granted[key]``."""
    def fn(quantum):
        q = min(quantum, duration)
        if granted is not None:
            granted[key] += q
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < q:
            pass
        return True
    return fn


def make(front=0.7, back=0.2, fcpu=0.05, idle=0.05, shards=1):
    cfg = small_test_config(scheduler=SchedulerConfig(
        cycle_ms=5.0, share_front=front, share_fcpu=fcpu, share_back=back,
        share_idle=idle, shards=shards))
    return HvScheduler(cfg)


def test_front_share_protected_under_back_flood():
    """BACK elasticity tasks must not starve the data plane (O1).

    Counted by the quanta the scheduler grants, not by wall time: on a
    loaded host the OS preempts the spinning thread, and a preemption
    inside a BACK slice charges BACK for time no task ran."""
    sched = make()
    granted = {FRONT: 0.0, BACK: 0.0}
    sched.add_task(0, "vcpu", FRONT, spin_task(1.0, granted, FRONT))
    for i in range(4):
        sched.add_task(0, f"swap{i}", BACK, spin_task(1.0, granted, BACK))
    sched.start()
    time.sleep(0.5)
    sched.stop()
    total = granted[FRONT] + granted[BACK]
    assert granted[FRONT] / total > 0.6, granted   # ~0.74-0.78 for 0.7/0.2


def test_unused_front_slices_flow_to_back():
    sched = make()
    # no FRONT tasks at all: BACK may exceed its static share
    sched.add_task(0, "swap", BACK, spin_task(1.0))
    sched.start()
    time.sleep(0.3)
    sched.stop()
    rt = sched.class_runtime()
    wall = 0.3
    assert rt["BACK"] > wall * 0.4, rt      # >> its 20% static share


def test_overrun_penalty_applied():
    sched = make()

    calls = []

    def hog(quantum):
        calls.append(quantum)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < quantum * 3:   # always overruns
            pass
        return True

    t = sched.add_task(0, "hog", BACK, hog)
    sched.start()
    time.sleep(0.2)
    sched.stop()
    assert t.overruns >= 1
    assert any(q < max(calls) * 0.9 for q in calls[1:]), \
        "penalty should shrink later quanta"


def test_one_shot_task_removed():
    sched = make()
    ran = []
    sched.add_task(0, "once", BACK, lambda q: (ran.append(1), False)[1])
    sched.start()
    time.sleep(0.1)
    sched.stop()
    assert len(ran) == 1


def test_hotplug_vcpu_gets_time():
    """CPU elasticity (§7.4): a hot-plugged VCPU runs under FCPU."""
    sched = make(front=0.5, fcpu=0.2, back=0.2, idle=0.1)
    sched.add_task(0, "vcpu0", FRONT, spin_task(1.0))
    t = sched.hotplug_vcpu(0, "vcpu1", spin_task(1.0))
    sched.start()
    time.sleep(0.3)
    sched.stop()
    assert t.runtime_s > 0.02, sched.class_runtime()


def test_back_disabled_shard_gives_time_to_front():
    sched = make(shards=1)
    sched.add_task(0, "vcpu", FRONT, spin_task(1.0))
    sched.add_task(0, "swap", BACK, spin_task(1.0))
    sched.set_back_enabled(0, False)
    sched.start()
    time.sleep(0.25)
    sched.stop()
    rt = sched.class_runtime()
    assert rt["BACK"] < 0.02, rt
