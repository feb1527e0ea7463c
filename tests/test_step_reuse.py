"""``run_training``'s step memo: one compiled step serves every call
that differs only in what the step takes as data (the seed's state and
batches, the learning rate), and any change to the program misses."""
import os
import sys
import threading
import time

import pytest

from repro.launch import train

ARCH = "stablelm-1.6b"
TINY = dict(smoke=True, steps=3, batch=2, seq=32, verbose=False, seed=7)


def _run(**kw):
    return train.run_training(kw.pop("arch", ARCH), **dict(TINY, **kw))


def test_one_executable_serves_two_learning_rates():
    train.clear_step_cache()
    hits0, misses0 = train.step_cache_counts()
    a = _run(lr=1e-3)
    b = _run(lr=3e-4)
    assert (a.step_reused, b.step_reused) == (False, True)
    assert train.step_cache_counts() == (hits0 + 1, misses0 + 1)
    # each as it runs with nothing in the memo, bit for bit
    train.clear_step_cache()
    a_alone = _run(lr=1e-3)
    train.clear_step_cache()
    b_alone = _run(lr=3e-4)
    assert not a_alone.step_reused and not b_alone.step_reused
    assert a.losses == a_alone.losses
    assert b.losses == b_alone.losses
    # the lr reaches the update: same first loss, different after it
    assert a.losses[0] == b.losses[0]
    assert a.losses[1:] != b.losses[1:]


@pytest.mark.parametrize("change", [dict(batch=4), dict(seq=16),
                                    dict(arch="gemma3-1b"),
                                    dict(quantized_moments=True)],
                         ids=["batch", "seq", "config", "optimizer"])
def test_a_different_program_misses(change):
    _run()
    _, misses0 = train.step_cache_counts()
    r = _run(**change)
    assert not r.step_reused
    assert train.step_cache_counts()[1] == misses0 + 1
    assert _run().step_reused


def test_a_replaced_builder_misses(monkeypatch):
    _run()
    sound_builder = train.make_train_step
    built = []

    def builder(*args, **kw):
        built.append(args)
        return sound_builder(*args, **kw)

    monkeypatch.setattr(train, "make_train_step", builder)
    replaced = _run()
    assert not replaced.step_reused and built
    assert _run().step_reused          # the replaced builder's own step
    monkeypatch.undo()
    sound = _run()
    assert sound.step_reused           # the module's builder's step again
    assert sound.losses == replaced.losses


def test_two_threads_with_one_key_compile_once():
    train.clear_step_cache()
    hits0, misses0 = train.step_cache_counts()
    results = [None, None]
    start = threading.Barrier(2)

    def job(i):
        start.wait()
        results[i] = _run(lr=(1e-3, 3e-4)[i])

    workers = [threading.Thread(target=job, args=(i,)) for i in range(2)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=300)
    assert not any(w.is_alive() for w in workers)
    assert train.step_cache_counts() == (hits0 + 1, misses0 + 1)
    assert sorted(r.step_reused for r in results) == [False, True]
    assert results[0].losses[0] == results[1].losses[0]


@pytest.mark.parametrize("resumed", [False, True],
                         ids=["no_steps", "resumed_at_the_end"])
def test_a_call_with_no_steps_left_compiles_nothing(resumed, tmp_path):
    """The step is looked up at the first batch: a call that runs no step
    neither compiles one nor asks the memo."""
    d = str(tmp_path / "run")
    if resumed:
        _run(steps=2, ckpt_dir=d, ckpt_every=2)
    train.clear_step_cache()
    counts0 = train.step_cache_counts()
    r = _run(steps=2 if resumed else 0, ckpt_dir=d if resumed else None)
    assert r.losses == [] and r.steps == 0
    assert (r.restored_from is not None) == resumed
    assert not r.step_reused and r.compile_seconds == 0.0
    assert r.lookup_seconds == 0.0
    assert train.step_cache_counts() == counts0
    assert not train._step_memo._steps


def test_memo_under_many_threads_compiles_each_key_once():
    """More threads than cores on a few keys, switching often: each key
    is compiled once, every other call finds it, and no count is lost."""
    memo = train._StepMemo(size=8)
    n = 2 * (os.cpu_count() or 4)
    compiles = []
    found = [None] * n

    def compile_step(key):
        compiles.append(key)
        time.sleep(0.01)            # a slow compile, so the others wait
        return ("step", key)

    def call(i):
        key = (i % 3,)
        compiled = memo.get(key)
        if compiled is None:
            compiled, _ = memo.compile_once(key,
                                            lambda: compile_step(key))
        found[i] = compiled

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=call, args=(i,))
                   for i in range(n)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert sorted(compiles) == [(0,), (1,), (2,)]
    assert found == [("step", (i % 3,)) for i in range(n)]
    assert memo.counts() == (n - 3, 3)


def test_memo_drops_the_least_recently_used():
    memo = train._StepMemo(size=2)
    for key in ((0,), (1,)):
        memo.compile_once(key, lambda: key)
    assert memo.get((0,)) == (0,)     # (1,) is now the oldest
    memo.compile_once((2,), lambda: (2,))
    assert memo.get((1,)) is None
    assert memo.get((0,)) == (0,) and memo.get((2,)) == (2,)


def test_resume_from_a_checkpoint_matches_the_uninterrupted_run(tmp_path):
    full = _run(steps=6)
    d = str(tmp_path / "run")
    _run(steps=3, ckpt_dir=d, ckpt_every=3)
    resumed = _run(steps=6, ckpt_dir=d, ckpt_every=3)
    assert resumed.restored_from is not None
    assert resumed.step_reused
    assert resumed.losses == full.losses[3:]


def test_the_compiled_step_keeps_its_module_name():
    """A device trace finds the steps by the module's name."""
    train.clear_step_cache()
    _run(steps=1)
    compiled, = train._step_memo._steps.values()
    assert compiled.as_text().startswith("HloModule jit_train_step,")
