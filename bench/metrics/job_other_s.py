"""job_other_s (s): mean time per job outside compiling and stepping:
the payload's wall time on the benchmark's host clock, less
``compile_seconds`` and the sum of ``step_seconds``.  It is mostly the
eager initialisation of parameters and moments, and host batches."""


def read(record):
    o = [j["end"] - j["start"] - j["compile_s"] - sum(j["step_s"])
         for j in record["jobs"] if j.get("error") is None]
    return sum(o) / len(o) if o else None
