"""``run_training``'s host spans on the profiler's timeline, the times it
reports beside them, its per-thread persistent-cache counters, and its
step memo as the spans and counters see it."""
import glob
import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import train

ARCH = "stablelm-1.6b"
TINY = dict(smoke=True, steps=2, batch=2, seq=32, verbose=False)
# what a run of two steps writes, in order, on the thread that called it:
# with its step compiled, and with its step found in the memo
SPANS = ["train.init", "train.batch", "train.lookup", "train.trace",
         "train.lower", "train.backend_compile", "train.step",
         "train.batch", "train.step"]
SPANS_REUSED = ["train.init", "train.batch", "train.lookup", "train.step",
                "train.batch", "train.step"]


@pytest.fixture(scope="module")
def no_memo():
    """An empty step memo, whatever ran before in this process: the next
    call compiles its step."""
    train.clear_step_cache()


def _traced_run(log_dir):
    """A tiny run under the profiler; its result and its ``train.*`` host
    events, by the thread (line) that wrote them."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        result = train.run_training(ARCH, **TINY)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("train.")]
            if evs:
                lines[(plane.name, line.name)] = sorted(
                    evs, key=lambda e: e[1])
    return result, lines


@pytest.fixture(scope="module")
def traced(no_memo, tmp_path_factory):
    return _traced_run(str(tmp_path_factory.mktemp("trace")))


@pytest.fixture(scope="module")
def traced_reused(traced, tmp_path_factory):
    """The same run again: its step is the memo's."""
    return _traced_run(str(tmp_path_factory.mktemp("trace_reused")))


def _assert_spans(lines, names):
    assert len(lines) == 1
    events, = lines.values()
    assert [name for name, _, _ in events] == names
    # each span ends before the next begins: none nests in another
    for (_, _, end), (_, start, _) in zip(events, events[1:]):
        assert end <= start


def test_spans_on_one_thread_in_order(traced):
    result, lines = traced
    assert not result.step_reused
    _assert_spans(lines, SPANS)


def test_reused_step_writes_no_compile_span(traced_reused):
    result, lines = traced_reused
    assert result.step_reused
    assert result.compile_seconds == 0.0
    _assert_spans(lines, SPANS_REUSED)


def test_span_times_in_the_result(traced):
    r, lines = traced
    assert r.trace_seconds > 0 and r.lower_seconds > 0
    assert r.backend_compile_seconds > 0 and r.init_seconds > 0
    assert r.lookup_seconds > 0
    assert r.compile_seconds == pytest.approx(
        r.trace_seconds + r.lower_seconds + r.backend_compile_seconds,
        rel=1e-12)
    assert len(r.batch_seconds) == len(r.step_seconds) == TINY["steps"]
    # the perf_counter clock runs inside each profiler span
    events, = lines.values()
    clocked = ([r.init_seconds, r.batch_seconds[0], r.lookup_seconds,
                r.trace_seconds, r.lower_seconds, r.backend_compile_seconds,
                r.step_seconds[0], r.batch_seconds[1], r.step_seconds[1]])
    for (name, start, end), s in zip(events, clocked):
        assert s * 1e9 <= (end - start) + 1e6, name


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in an empty directory, every
    program written to it; the configuration is restored afterwards."""
    keys = ["jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes"]
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_cold_cache_misses_then_hits(persistent_cache):
    train.clear_step_cache()
    cold = train.run_training(ARCH, **TINY)
    assert cold.cache_requests > cold.cache_hits
    # with the memo emptied the step is compiled again: it reaches the
    # cache again, and hits
    train.clear_step_cache()
    warm = train.run_training(ARCH, **TINY)
    assert not warm.step_reused
    assert warm.cache_requests >= 1
    assert warm.cache_hits == warm.cache_requests
    assert warm.losses == cold.losses


def test_second_call_asks_the_cache_nothing(persistent_cache):
    train.clear_step_cache()
    first = train.run_training(ARCH, **TINY)
    second = train.run_training(ARCH, **TINY)
    assert not first.step_reused and first.cache_requests >= 1
    assert second.step_reused
    assert second.cache_requests == second.cache_hits == 0
    assert second.losses == first.losses


def test_cache_counts_are_per_thread(persistent_cache):
    """Threads compiling at once, more of them than cores, each count
    their own request and nothing of the others'."""
    x = jax.ShapeDtypeStruct((3,), jnp.float32)
    # JAX decides once, at the first compile after a reset, whether the
    # cache is in use; threads that race that first decision skip it
    jax.jit(lambda x: x - 2.5).lower(x).compile()
    before = train._cache_counts()
    n = 2 * (os.cpu_count() or 4)
    seen = [None] * n

    def compile_one(i):
        first = train._cache_counts()
        jax.jit(lambda x: x * (i + 0.25) + 1.5).lower(x).compile()
        seen[i] = (first, train._cache_counts())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=compile_one, args=(i,))
                   for i in range(n)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    for first, after in seen:
        assert after[0] == first[0] + 1      # its own request, no other
    assert train._cache_counts() == before
