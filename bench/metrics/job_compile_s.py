"""job_compile_s (s): mean ``compile_seconds`` per job, the program's
clock around tracing, lowering and compiling (or loading from the
persistent cache) each job's train step."""


def read(record):
    c = [j["compile_s"] for j in record["jobs"] if j.get("error") is None]
    return sum(c) / len(c) if c else None
