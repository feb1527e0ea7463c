"""Record the small device trace ``test_bench_devtrace.py`` reduces.

    python3 bench/tests/record_trace.py bench/tests/data/small_trace.xplane.pb

Needs the chip.  It writes the spans a sweep run writes (``window`` and
one ``payload <job>`` per job) around a few calls of a jitted
``train_step``, with host-side pauses of known length between them: a
dispatch gap before each payload, a set-up pause before a payload's
first step and a pause between steps.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

PAUSE = {"dispatch": 0.30, "setup": 0.20, "between": 0.10}


def main(out: str) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import jax
    import jax.numpy as jnp
    from devtrace import Capture, annotate
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2

    def train_step(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    step = jax.jit(train_step)
    x = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    step(x).block_until_ready()
    capture = Capture()
    try:
        capture.start()
        with annotate("window", True):
            for job in ("j00000", "j00001"):
                time.sleep(PAUSE["dispatch"])
                with annotate(f"payload {job}", True):
                    time.sleep(PAUSE["setup"])
                    for _ in range(3):
                        step(x).block_until_ready()
                        time.sleep(PAUSE["between"])
            time.sleep(PAUSE["dispatch"])
        path = capture.stop()
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, out)
    finally:
        capture.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
