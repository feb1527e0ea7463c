"""Append-only experiment journal (the paper: "the parametric engine ...
ensures that the state is recorded in persistent storage. This allows the
experiment to be restarted if the node running Nimrod goes down").

Events are JSON lines, fsync'd on write.  Restart = replay.  A torn final
line (crash mid-write) is detected and dropped.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Iterator, List, Optional


def stable_dumps(obj: Any) -> str:
    """Canonical JSON for journals and trace exports: sorted keys and
    exact (shortest round-trip) float reprs, so two same-seed runs
    serialize byte-identically.  The journal has always written this
    format; the telemetry JSONL exporter shares it."""
    return json.dumps(obj, sort_keys=True)


def left_sum(values) -> float:
    """Plain left-to-right float sum, rounded after every addition.

    Python 3.12's ``sum()`` compensates float rounding, so its result can
    differ in the last bit from this fold.  Every float total that reaches
    ``stable_repr``, a journal or a bank total goes through here, so those
    bytes do not depend on the interpreter version."""
    total = 0
    for v in values:
        total = total + v
    return total


# tail window read when recovering ``seq`` on reopen; grows geometrically
# if the last well-formed line is longer than this (rare: one event)
_TAIL_BLOCK = 64 * 1024


def _recover_tail(path: str) -> int:
    """Next sequence number, recovered from the LAST well-formed journal
    line — O(tail), not O(file): a month-long experiment's restart must
    not re-parse every event ever written just to learn one integer.

    A torn trailing fragment (crash mid-write) is truncated here, so the
    next append starts a fresh line instead of gluing onto the fragment
    and corrupting an otherwise-good event."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return 0
    if size == 0:
        return 0
    with open(path, "r+b") as f:
        # drop an unterminated trailing fragment first (no final "\n")
        block = min(_TAIL_BLOCK, size)
        while True:
            f.seek(size - block)
            data = f.read(block)
            if b"\n" in data or block == size:
                break
            block = min(block * 2, size)
        if not data.endswith(b"\n"):
            body, nl, _frag = data.rpartition(b"\n")
            if nl:
                size = size - block + len(body) + 1
            else:                       # whole file is one torn fragment
                size = 0
            f.truncate(size)
        if size == 0:
            return 0
        # walk complete lines backwards until one parses with a seq
        block = min(_TAIL_BLOCK, size)
        while True:
            start = size - block
            f.seek(start)
            data = f.read(block)
            lines = data.split(b"\n")
            # the window's first chunk may be a mid-line cut: only trust
            # it when the window starts at the top of the file
            trusted = lines if start == 0 else lines[1:]
            for line in reversed(trusted):
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue            # torn-but-terminated line: skip
                if isinstance(ev, dict) and isinstance(ev.get("seq"), int):
                    return ev["seq"] + 1
            if start == 0:
                return 0                # nothing well-formed anywhere
            block = min(block * 2, size)


class Journal:
    def __init__(self, path: str, fsync: bool = True):
        self.path = path
        self.fsync = fsync
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._seq = self._count_existing()
        self._f = open(path, "a", encoding="utf-8")

    def _count_existing(self) -> int:
        # recover from the last well-formed line (and clip a torn tail)
        # BEFORE opening the append handle — O(tail) however large the
        # journal has grown
        if os.path.exists(self.path):
            return _recover_tail(self.path)
        return 0

    def append(self, kind: str, **fields: Any) -> Dict[str, Any]:
        ev = {"seq": self._seq, "kind": kind, **fields}
        self._f.write(stable_dumps(ev) + "\n")
        self._f.flush()
        if self.fsync:
            os.fsync(self._f.fileno())
        self._seq += 1
        return ev

    def close(self) -> None:
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def replay(path: str) -> Iterator[Dict[str, Any]]:
    """Yield events; silently drop a torn trailing line."""
    if not os.path.exists(path):
        return
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                return  # torn tail — crash mid-write; ignore the fragment


def load_events(path: str) -> List[Dict[str, Any]]:
    return list(replay(path))
