"""Training driver (single-process; any arch at smoke or full scale).

Real training on the local device(s) with the full substrate: synthetic
data pipeline, AdamW + cosine schedule, sharded checkpoint save/restore
with exact data-position resume — the per-job payload the Nimrod/G grid
schedules and restarts.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/run1 --ckpt-every 20

``run_training`` marks its phases as host spans on the profiler's
timeline (``jax.profiler.TraceAnnotation``, a no-op with no profiler
running), each on the calling thread and each also timed into the
``TrainResult``:

- ``train.init``: entry to the first batch (configuration, mesh, data
  stream, eager initialisation or restore of the state, the ``jit``
  wrapper), host time only;
- ``train.trace``, ``train.lower``, ``train.backend_compile``: the step
  traced to a jaxpr, lowered to StableHLO, and compiled by XLA or loaded
  from the persistent compilation cache, once per call;
- ``train.batch``: each step's host batch and its transfer;
- ``train.step``: each step's compiled call up to ``block_until_ready``.

It also counts, on the calling thread, the programs it hands to XLA
through the persistent compilation cache and the cache's hits.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.checkpoint import latest_step_dir, load_metadata, restore, save
from repro.configs import get_config, smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, abstract_opt_state, init_opt_state
from repro.train.steps import make_train_step


@dataclasses.dataclass
class TrainResult:
    steps: int
    final_loss: float
    losses: list
    tokens_per_sec: float             # over the timed steps, compile excluded
    restored_from: Optional[str] = None
    compile_seconds: float = 0.0      # trace + lower + compile of the step
    step_seconds: list = dataclasses.field(default_factory=list)
    trace_seconds: float = 0.0        # the step traced to a jaxpr
    lower_seconds: float = 0.0        # the jaxpr lowered to StableHLO
    backend_compile_seconds: float = 0.0  # XLA compile, or cache load
    init_seconds: float = 0.0         # entry to the first batch, host time
    batch_seconds: list = dataclasses.field(default_factory=list)
    cache_requests: int = 0           # programs handed to the persistent
    cache_hits: int = 0               # cache, and those it held


# JAX's persistent-cache events, counted per thread: the payloads of a
# multi-slot executor run concurrently, each on its own thread
CACHE_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HITS = "/jax/compilation_cache/cache_hits"


class _CacheCounts(threading.local):
    requests = 0
    hits = 0


_counts = _CacheCounts()
_listening = False
_listen_lock = threading.Lock()


def _count_cache_event(event: str, **_) -> None:
    if event == CACHE_REQUESTS:
        _counts.requests += 1
    elif event == CACHE_HITS:
        _counts.hits += 1


def _cache_counts() -> Tuple[int, int]:
    """This thread's cache requests and hits so far; the listener is
    registered with ``jax.monitoring`` on the first call."""
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_listener(_count_cache_event)
            _listening = True
    return _counts.requests, _counts.hits


@contextlib.contextmanager
def _span(name: str, spans: Dict[str, list]):
    """A host span on the profiler's timeline, its length on the
    ``perf_counter`` clock appended to ``spans[name]``."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        yield
        spans[name].append(time.perf_counter() - t0)


def run_training(arch: str, *, smoke: bool = True, steps: int = 50,
                 batch: int = 8, seq: int = 256, lr: float = 1e-3,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 seed: int = 0, log_every: int = 10,
                 quantized_moments: bool = False,
                 verbose: bool = True) -> TrainResult:
    requests0, hits0 = _cache_counts()
    spans: Dict[str, list] = collections.defaultdict(list)
    with _span("train.init", spans):
        cfg = smoke_config(arch) if smoke else get_config(arch)
        mesh = make_local_mesh()
        opt_cfg = AdamWConfig(lr=lr, quantized_moments=quantized_moments)
        data = SyntheticLM(DataConfig(
            vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
            seed=seed, input_kind=cfg.input_kind, d_model=cfg.d_model))

        start_step = 0
        restored_from = None
        params = opt_state = None
        if ckpt_dir:
            last = latest_step_dir(ckpt_dir)
            if last is not None:
                meta = load_metadata(last)
                start_step = int(meta["step"])
                aparams = tfm.abstract_model(cfg)
                params = restore(os.path.join(last, "params"), aparams)
                aopt = abstract_opt_state(aparams, opt_cfg)
                opt_state = restore(os.path.join(last, "opt"), aopt)
                restored_from = last
                if verbose:
                    print(f"restored step {start_step} from {last}")
        if params is None:
            params = tfm.init_model(cfg, jax.random.PRNGKey(seed))
            opt_state = init_opt_state(params, opt_cfg)

        # params and optimizer state are donated: the update writes into
        # the old buffers instead of holding two copies of both across a
        # step
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, mesh=mesh,
                                          total_steps=max(steps, 100)),
                          donate_argnums=(0, 1))
    compiled = None
    losses = []
    tokens = 0
    for step in range(start_step, steps):
        with _span("train.batch", spans):
            b = data.batch(step)
            batch_dev = {k: jax.numpy.asarray(v) for k, v in b.items()}
        if compiled is None:
            with _span("train.trace", spans):
                traced = step_fn.trace(params, opt_state, batch_dev)
            with _span("train.lower", spans):
                lowered = traced.lower()
            with _span("train.backend_compile", spans):
                compiled = lowered.compile()
        with _span("train.step", spans):
            params, opt_state, metrics = compiled(params, opt_state,
                                                  batch_dev)
            jax.block_until_ready((params, opt_state, metrics))
        loss = float(metrics["loss"])
        losses.append(loss)
        tokens += batch * seq
        if verbose and (step % log_every == 0 or step == steps - 1):
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f}", flush=True)
        if not np.isfinite(loss):
            raise FloatingPointError(f"loss diverged at step {step}")
        if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0:
            d = os.path.join(ckpt_dir, f"step_{step + 1:07d}")
            save(os.path.join(d, "params"), params,
                 metadata={"step": step + 1, "arch": arch})
            save(os.path.join(d, "opt"), opt_state,
                 metadata={"step": step + 1})
            with open(os.path.join(d, "manifest.json"), "w") as f:
                json.dump({"metadata": {"step": step + 1, "arch": arch},
                           "entries": [], "crcs": {}}, f)
            if verbose:
                print(f"checkpointed -> {d}")
    requests, hits = _cache_counts()
    trace_s, lower_s, backend_s = (
        sum(spans[n]) for n in ("train.trace", "train.lower",
                                "train.backend_compile"))
    step_seconds = spans["train.step"]
    return TrainResult(steps=steps - start_step,
                       final_loss=losses[-1] if losses else float("nan"),
                       losses=losses,
                       tokens_per_sec=tokens / max(sum(step_seconds), 1e-9),
                       restored_from=restored_from,
                       compile_seconds=trace_s + lower_s + backend_s,
                       step_seconds=step_seconds,
                       trace_seconds=trace_s,
                       lower_seconds=lower_s,
                       backend_compile_seconds=backend_s,
                       init_seconds=sum(spans["train.init"]),
                       batch_seconds=spans["train.batch"],
                       cache_requests=requests - requests0,
                       cache_hits=hits - hits0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantized-moments", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()
    r = run_training(args.arch, smoke=args.smoke, steps=args.steps,
                     batch=args.batch, seq=args.seq, lr=args.lr,
                     ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                     seed=args.seed,
                     quantized_moments=args.quantized_moments)
    print(f"done: {r.steps} steps, final_loss={r.final_loss:.4f}, "
          f"{r.tokens_per_sec:,.0f} tok/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
