"""Journal + exact experiment restart (the paper's persistence contract)."""
import json
import os

import pytest

from repro.core import (Dispatcher, Journal, NimrodG, PriceSchedule,
                        ResourceDirectory, SchedulerConfig, SimulatedExecutor,
                        Simulator, TradeServer, UserRequirements,
                        gusto_like_testbed, load_events, parse_plan)

HOUR = 3600.0

PLAN = """
parameter i integer range from 1 to 20 step 1
task main
    execute run --i $i
endtask
"""


def _build(tmp_path, journal_name="journal.jsonl", horizon_stop=None,
           seed=0):
    directory = ResourceDirectory()
    for spec in gusto_like_testbed(10, seed=2):
        directory.register(spec)
    schedules = {n: PriceSchedule(directory.spec(n))
                 for n in directory.all_names()}
    trade = TradeServer(directory, schedules)
    sim = Simulator()
    ex = SimulatedExecutor(sim, directory, seed=seed)
    disp = Dispatcher(ex, directory)
    req = UserRequirements(deadline=20 * HOUR, budget=1e5, strategy="cost")
    journal = Journal(str(tmp_path / journal_name))
    eng = NimrodG.from_plan("restartable", parse_plan(PLAN), req, directory,
                            trade, disp, est_seconds=lambda p: 1800.0,
                            sim=sim, journal=journal, seed=seed)
    return eng, sim


def test_journal_records_lifecycle(tmp_path):
    eng, sim = _build(tmp_path)
    rep = eng.run_simulated(failures=False)
    assert rep.n_done == 20
    events = load_events(str(tmp_path / "journal.jsonl"))
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "EXP_CREATED"
    assert kinds.count("JOB_CREATED") == 20
    assert kinds.count("DONE") >= 20
    assert "EXP_DONE" in kinds
    assert kinds.count("DISPATCH") >= 20
    # every DONE has a matching DISPATCH
    dispatched = {e["job_id"] for e in events if e["kind"] == "DISPATCH"}
    done = {e["job_id"] for e in events if e["kind"] == "DONE"}
    assert done <= dispatched


def test_restart_resumes_not_repeats(tmp_path):
    # phase 1: run the experiment but kill it (stop sim) partway
    eng, sim = _build(tmp_path)
    eng.sim.after(0.0, eng.tick)
    sim.run(until=2.2 * HOUR)       # "node running Nimrod goes down"
    done_before = sum(1 for e in load_events(str(tmp_path / "journal.jsonl"))
                      if e["kind"] == "DONE")
    assert 0 < done_before < 20
    eng.journal.close()

    # phase 2: new engine (fresh process), restore from the journal
    eng2, sim2 = _build(tmp_path, journal_name="journal2.jsonl")
    recovered = eng2.restore_from(str(tmp_path / "journal.jsonl"))
    assert recovered == done_before
    rep = eng2.run_simulated(failures=False)
    assert rep.n_done == 20
    # the restarted engine only ran the remainder
    redone = sum(1 for e in load_events(str(tmp_path / "journal2.jsonl"))
                 if e["kind"] == "DONE")
    assert redone == 20 - recovered
    # spend carried over
    assert rep.total_cost >= eng2.ledger.settled - 1e-9


def test_torn_tail_line_is_ignored(tmp_path):
    p = tmp_path / "j.jsonl"
    with Journal(str(p)) as j:
        j.append("EXP_CREATED", n_jobs=1, deadline=1.0, budget=1.0,
                 strategy="cost", user="u")
        j.append("DONE", job_id="j00000", cost=2.5)
    with open(p, "a") as f:
        f.write('{"kind": "DONE", "job_id": "j00001", "co')  # torn write
    events = load_events(str(p))
    assert len(events) == 2
    st = NimrodG.replay_journal(str(p))
    assert st["done"] == {"j00000": 2.5}
    assert st["spent"] == 2.5


def test_duplicate_done_events_counted_once(tmp_path):
    p = tmp_path / "j.jsonl"
    with Journal(str(p)) as j:
        j.append("DONE", job_id="j00000", cost=2.0)
        j.append("DONE", job_id="j00000~1", cost=1.0)   # duplicate attempt
    st = NimrodG.replay_journal(str(p))
    assert st["done"] == {"j00000": 2.0}
    assert st["spent"] == 2.0


def test_journal_seq_monotonic_across_reopen(tmp_path):
    p = str(tmp_path / "j.jsonl")
    with Journal(p) as j:
        j.append("A")
        j.append("B")
    with Journal(p) as j:
        j.append("C")
    seqs = [e["seq"] for e in load_events(p)]
    assert seqs == [0, 1, 2]


def test_reopen_large_journal_reads_only_the_tail(tmp_path):
    # seq recovery must be O(tail), not O(file): build a journal far
    # larger than the tail window and prove reopen never reads most of
    # it (a read-counting file object would be invasive; instead bound
    # wall work by checking the recovered seq is exact and the torn-
    # tail clip logic leaves earlier bytes untouched)
    import json as _json

    from repro.core.persistence import _TAIL_BLOCK, _recover_tail

    p = str(tmp_path / "big.jsonl")
    n = 50_000
    with open(p, "w") as f:
        for i in range(n):
            f.write(_json.dumps({"seq": i, "kind": "E",
                                 "pad": "x" * 64}) + "\n")
    size = os.path.getsize(p)
    assert size > 20 * _TAIL_BLOCK      # genuinely larger than one block
    assert _recover_tail(p) == n
    with Journal(p) as j:
        ev = j.append("NEXT")
    assert ev["seq"] == n
    assert os.path.getsize(p) > size    # append-only: nothing rewritten


def test_reopen_after_torn_tail_recovers_seq_and_clips_fragment(tmp_path):
    p = str(tmp_path / "j.jsonl")
    with Journal(p) as j:
        for _ in range(5):
            j.append("E")
    with open(p, "a") as f:
        f.write('{"seq": 5, "kind": "E", "tr')    # crash mid-write
    with Journal(p) as j:
        ev = j.append("AFTER")
    # the torn fragment was clipped, not glued onto the new line
    events = load_events(p)
    assert [e["seq"] for e in events] == [0, 1, 2, 3, 4, 5]
    assert events[-1]["kind"] == "AFTER"
    assert ev["seq"] == 5


def test_reopen_torn_tail_without_any_newline(tmp_path):
    p = str(tmp_path / "j.jsonl")
    with open(p, "w") as f:
        f.write('{"seq": 0, "ki')                 # torn very first line
    with Journal(p) as j:
        j.append("FIRST")
    events = load_events(p)
    assert [(e["seq"], e["kind"]) for e in events] == [(0, "FIRST")]


def test_recover_tail_skips_lines_without_int_seq(tmp_path):
    p = str(tmp_path / "j.jsonl")
    with open(p, "w") as f:
        f.write('{"seq": 7, "kind": "E"}\n')
        f.write('["not", "a", "dict"]\n')         # well-formed, wrong shape
        f.write('{"kind": "no_seq"}\n')
    with Journal(p) as j:
        ev = j.append("NEXT")
    assert ev["seq"] == 8                          # last line WITH a seq


@pytest.mark.parametrize("values,want", [
    ([1e16, 1.0, -1e16], 0.0),          # 1.0 is absorbed, then cancelled
    ([0.1] * 10, 0.9999999999999999),   # each addition rounds
    ([], 0),
])
def test_left_sum_rounds_after_every_addition(values, want):
    from repro.core.persistence import left_sum
    got = left_sum(values)
    assert got == want and type(got) is type(want)
    # a plain fold: the order of the additions is the order of the input
    acc = 0
    for v in values:
        acc += v
    assert got == acc
