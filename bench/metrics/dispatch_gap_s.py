"""dispatch_gap_s (s): mean time the chip's slot waits for the broker:
from the window's start to the first payload's start, and from each
payload's end to the next one's start, on the benchmark's host clock."""


def read(record):
    jobs = sorted(record["jobs"], key=lambda j: j["start"])
    if not jobs:
        return None
    gaps = [jobs[0]["start"] - record["t0"]]
    gaps += [b["start"] - a["end"] for a, b in zip(jobs, jobs[1:])]
    return sum(gaps) / len(gaps)
