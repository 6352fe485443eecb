"""The stall watch keeps where the host stood in a long tick, and only there."""
import time

from bench.harness.stalls import StallWatch


def sleep_in_a_named_place(seconds):
    time.sleep(seconds)


def test_a_long_tick_is_sampled_and_a_short_one_is_not():
    watch = StallWatch(after=0.1, every=0.02)
    watch.start()
    try:
        watch.tick(0)
        time.sleep(0.01)
        watch.tick(None)
        watch.tick(1)
        sleep_in_a_named_place(0.4)
        watch.tick(None)
    finally:
        watch.stop()
    assert watch.report(0) == []
    stacks = watch.report(1)
    assert stacks and all("sleep_in_a_named_place" in s for s, _ in stacks)
    assert sum(n for _, n in stacks) >= 5
    assert watch.samples.keys() == {1}


def test_the_counters_around_a_tick_show_where_the_time_went():
    watch = StallWatch(after=0.1, every=0.02)
    watch.start()
    try:
        time.sleep(0.05)
        a = time.perf_counter()
        end = a + 0.2
        while time.perf_counter() < end:      # the watched thread on the CPU
            pass
        b = time.perf_counter()
        time.sleep(0.05)
    finally:
        watch.stop()
    host = watch.host(a, b)
    assert host["span_s"] >= b - a
    assert host["watch_gap_s"] < 0.2          # the process ran all along
    if "thread_cpu_s" in host:                # Linux: the thread's own time
        assert host["thread_cpu_s"] > 0.1
    assert watch.host(b + 10, b + 11) == {}
