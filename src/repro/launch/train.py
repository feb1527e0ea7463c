"""Training driver (single-process; any arch at smoke or full scale).

Real training on the local device(s) with the full substrate: synthetic
data pipeline, AdamW + cosine schedule, sharded checkpoint save/restore
with exact data-position resume — the per-job payload the Nimrod/G grid
schedules and restarts.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/run1 --ckpt-every 20

The compiled train step is kept per process and reused across calls: a
parametric sweep's jobs differ in seed and learning rate, and neither
changes the program.  The step takes the learning rate as a float32
argument, ``(params, opt_state, batch, lr)``, and is memoised (a small
LRU) under a key of what does change the program: the step builder
(``make_train_step`` as this module holds it at call time), the model
config, the optimizer config less its ``lr``, the mesh, the schedule's
``total_steps`` and ``warmup``, and the tree structure and each leaf's
shape, dtype and sharding of the params, the optimizer state and the
first batch.  A call whose key is held runs the stored executable: no
trace, no lowering, no compile or cache load.  ``clear_step_cache``
empties the memo; ``step_cache_counts`` reads its hits and misses.

``run_training`` marks its phases as host spans on the profiler's
timeline (``jax.profiler.TraceAnnotation``, a no-op with no profiler
running), each on the calling thread and each also timed into the
``TrainResult``:

- ``train.init``: entry to the first batch (configuration, mesh, data
  stream, initialisation or restore of the state, waited for on the
  device);
- ``train.lookup``: after the first batch, the memo's key computed and
  looked up;
- ``train.trace``, ``train.lower``, ``train.backend_compile``: on a miss
  only, the step traced to a jaxpr, lowered to StableHLO, and compiled by
  XLA or loaded from the persistent compilation cache;
- ``train.batch``: each step's host batch and its transfer;
- ``train.step``: each step's compiled call up to ``block_until_ready``.

``TrainResult.step_reused`` says whether the call found its step in the
memo.  It also counts, on the calling thread, the programs it hands to
XLA through the persistent compilation cache and the cache's hits.

A mixture-of-experts model's step also returns its routing counts
(``models.moe.COUNTS``, summed over the MoE layers): they go into
``TrainResult.moe_counts``, one dict per step, and into a process-wide
tally over every call, read by ``routing_counts``.  The norm of the
routed experts' gradients goes into ``TrainResult.moe_expert_gnorms``.
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
from repro.models import moe as moe_mod
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
    lookup_seconds: float = 0.0       # the step memo's key and lookup
    step_reused: bool = False         # the step came from the memo
    # per step, MoE only: pairs routed to held experts, kept, dropped, and
    # the largest held expert's load, each summed over the MoE layers
    moe_counts: list = dataclasses.field(default_factory=list)
    # per step, MoE only: the norm of the routed experts' gradients
    moe_expert_gnorms: list = dataclasses.field(default_factory=list)


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


class _StepMemo:
    """Compiled train steps by what makes the program, the least recently
    used dropped past ``size``.  Misses compile one at a time, so threads
    after one step compile it once."""

    def __init__(self, size: int):
        self.size = size
        self._steps: "collections.OrderedDict[tuple, Any]" = (
            collections.OrderedDict())
        self._lock = threading.Lock()          # the table and the counts
        self._compile_lock = threading.Lock()  # the miss path
        self._hits = self._misses = 0

    def get(self, key: tuple):
        """The compiled step under ``key``, counted as a hit, or None."""
        with self._lock:
            compiled = self._steps.get(key)
            if compiled is not None:
                self._steps.move_to_end(key)
                self._hits += 1
            return compiled

    def compile_once(self, key: tuple, compile_step) -> Tuple[Any, bool]:
        """After a miss: the step under ``key`` and whether it was found,
        compiled by ``compile_step`` unless a thread that held the lock
        first compiled it."""
        with self._compile_lock:
            compiled = self.get(key)
            if compiled is not None:
                return compiled, True
            compiled = compile_step()
            with self._lock:
                self._misses += 1
                self._steps[key] = compiled
                while len(self._steps) > self.size:
                    self._steps.popitem(last=False)
            return compiled, False

    def counts(self) -> Tuple[int, int]:
        with self._lock:
            return self._hits, self._misses

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()


# each held step keeps its compiled code on the device: 41 MB for a
# 12-layer StableLM-2-1.6B step on a TPU v5e
_step_memo = _StepMemo(size=8)


def step_cache_counts() -> Tuple[int, int]:
    """The step memo's hits and misses so far, over every thread."""
    return _step_memo.counts()


def clear_step_cache() -> None:
    """Forget every compiled step; the counts keep running."""
    _step_memo.clear()


class _RoutingTally:
    """Routing counts summed over every MoE step of the process."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = dict.fromkeys(("steps",) + moe_mod.COUNTS, 0)

    def add(self, counts: Dict[str, int]) -> None:
        with self._lock:
            self._totals["steps"] += 1
            for k in moe_mod.COUNTS:
                self._totals[k] += counts[k]

    def read(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._totals)


_routing = _RoutingTally()


def routing_counts() -> Dict[str, int]:
    """MoE steps run so far in this process and their routing counts,
    summed over steps and layers (``models.moe.COUNTS``)."""
    return _routing.read()


def _signature(tree) -> tuple:
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple((x.shape, x.dtype, x.weak_type, x.sharding)
                          for x in leaves)


def _lr_step(builder, cfg, opt_cfg, mesh, total_steps: int, warmup: int):
    """The builder's step with the learning rate as its last argument.
    params and optimizer state are donated: the update writes into the
    old buffers instead of holding two copies of both across a step.  The
    compiled module keeps the name ``jit_train_step``, by which a device
    trace finds the steps."""
    def train_step(params, opt_state, batch, lr):
        return builder(cfg, dataclasses.replace(opt_cfg, lr=lr), mesh=mesh,
                       total_steps=total_steps, warmup=warmup)(
                           params, opt_state, batch)
    return jax.jit(train_step, donate_argnums=(0, 1))


def _compiled_step(cfg, opt_cfg, mesh, total_steps: int, args: tuple,
                   spans: Dict[str, list]) -> Tuple[Any, bool]:
    """The compiled step for ``args`` (params, opt_state, batch, lr) and
    whether it came from the memo."""
    warmup = 100
    with _span("train.lookup", spans):
        # the builder as this module holds it now, so that a replaced
        # one is a different program
        builder = make_train_step
        key = (builder, cfg, dataclasses.replace(opt_cfg, lr=None), mesh,
               total_steps, warmup, _signature(args[:3]))
        compiled = _step_memo.get(key)
    if compiled is not None:
        return compiled, True

    def compile_step():
        step_fn = _lr_step(builder, cfg, opt_cfg, mesh, total_steps, warmup)
        with _span("train.trace", spans):
            traced = step_fn.trace(*args)
        with _span("train.lower", spans):
            lowered = traced.lower()
        with _span("train.backend_compile", spans):
            return lowered.compile()
    return _step_memo.compile_once(key, compile_step)


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
        lr_arg = jax.device_put(np.float32(lr))
        # the eager initialisation runs here, not under the first step
        jax.block_until_ready((params, opt_state, lr_arg))

    compiled = None
    step_reused = False
    losses = []
    moe_counts, moe_expert_gnorms = [], []
    tokens = 0
    for step in range(start_step, steps):
        with _span("train.batch", spans):
            b = data.batch(step)
            batch_dev = {k: jax.numpy.asarray(v) for k, v in b.items()}
        if compiled is None:
            compiled, step_reused = _compiled_step(
                cfg, opt_cfg, mesh, max(steps, 100),
                (params, opt_state, batch_dev, lr_arg), spans)
        with _span("train.step", spans):
            params, opt_state, metrics = compiled(params, opt_state,
                                                  batch_dev, lr_arg)
            jax.block_until_ready((params, opt_state, metrics))
        loss = float(metrics["loss"])
        losses.append(loss)
        if cfg.moe is not None:
            got = jax.device_get({k: metrics[k] for k in
                                  moe_mod.COUNTS + ("moe_expert_gnorm",)})
            counts = {k: int(got[k]) for k in moe_mod.COUNTS}
            moe_counts.append(counts)
            moe_expert_gnorms.append(float(got["moe_expert_gnorm"]))
            _routing.add(counts)
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
        sum(spans[n], 0.0) for n in ("train.trace", "train.lower",
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
                       cache_hits=hits - hits0,
                       lookup_seconds=sum(spans["train.lookup"]),
                       step_reused=step_reused,
                       moe_counts=moe_counts,
                       moe_expert_gnorms=moe_expert_gnorms)


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
