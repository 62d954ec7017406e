"""The measured window's arithmetic and the compile counter."""

import bench_helpers  # noqa: F401  (puts the checkout on sys.path)

from bench.harness import CompileCounter, run_window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def passes_of(clock, durations):
    it = iter(durations)

    def one_pass():
        clock.t += next(it)
        return clock.t
    return one_pass


def test_window_ends_with_first_pass_at_or_after_seconds():
    clock = FakeClock()
    w = run_window(passes_of(clock, [3.0, 3.0, 3.0, 3.0]), 5.0, clock)
    assert w.ends == [3.0, 6.0]
    assert w.seconds == 6.0
    assert w.per_pass == 3.0
    assert len(w.results) == 2


def test_pass_straddling_seconds_is_counted_whole():
    clock = FakeClock()
    w = run_window(passes_of(clock, [4.0, 4.0, 4.0]), 6.0, clock)
    # the second pass starts before 6 s and ends after it: it is in
    assert w.ends == [4.0, 8.0]
    assert w.per_pass == 4.0


def test_pass_ending_exactly_at_seconds_closes_window():
    clock = FakeClock()
    w = run_window(passes_of(clock, [2.0, 2.0, 2.0]), 4.0, clock)
    assert w.ends == [2.0, 4.0]


def test_one_long_pass_fills_the_window():
    clock = FakeClock()
    w = run_window(passes_of(clock, [46.0, 46.0]), 30.0, clock)
    assert w.ends == [46.0]
    assert w.per_pass == 46.0


def test_uneven_passes_are_averaged_over_the_window():
    clock = FakeClock()
    w = run_window(passes_of(clock, [1.0, 5.0, 3.0]), 7.0, clock)
    assert w.ends == [1.0, 6.0, 9.0]
    assert w.per_pass == 3.0


def test_compile_counter_counts_compiles_inside_bounds():
    import jax
    import numpy as np

    clock = FakeClock()
    cc = CompileCounter(clock)
    clock.t = 10.0
    jax.jit(lambda x: x * 3 + 1)(np.arange(7)).block_until_ready()
    clock.t = 20.0
    jax.jit(lambda x: x * 5 - 2)(np.arange(9)).block_until_ready()
    assert cc.between(5.0, 15.0) == 1
    assert cc.between(5.0, 25.0) == 2
    assert cc.between(21.0, 30.0) == 0
