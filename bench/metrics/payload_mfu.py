"""payload_mfu (%): the train step's share of the chip's bf16 peak.

Model FLOPs of every step the window's jobs ran (``bench/flops.py``)
over the sum of their ``step_seconds`` (the program's own clock around
each step, which ends in ``block_until_ready``) times the peak.
"""


def read(record):
    steps = [s for j in record["jobs"] if j.get("error") is None
             for s in j["step_s"]]
    if not steps or record["peak"] is None:
        return None
    return (100.0 * record["flops_per_step"] * len(steps)
            / (sum(steps) * record["peak"]["bf16_flops"]))
