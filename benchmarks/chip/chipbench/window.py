"""What the benchmark records on the host clock while the engine serves, and
the arithmetic that turns it into end-to-end numbers.

The engine is driven through three public hooks only:

* each request's ``tokens`` list is a `TimedTokens`, so every emitted token
  (and every token regenerated after a rollback) is seen with its time;
* the engine's ``sampler`` is wrapped, so the end of each pass that
  samples is seen (sampling reads the logits back, so the pass's device
  work is done by then);
* the installed fault injector sees the ``engine.step`` point, which the
  engine fires for every request of a pass just before the pass runs.

From those, `Recorder` keeps the first-emission time of every token and
every pipeline pass (kind, host interval, batch, model FLOPs).
The pure functions below compute the window's numbers from them; the tests
drive them with synthetic timestamps.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import counters


class WindowClosed(Exception):
    """Raised from a hook when the window's time is up.  Deliberately not a
    RuntimeError: the engine takes a RuntimeError for a worker death."""


@dataclass
class Pass:
    kind: str                          # "decode" | "prefill"
    t0: float                          # engine.step fire (host clock)
    t1: Optional[float] = None         # first sampler return, or next fire
    rids: List[int] = field(default_factory=list)
    flops: int = 0

    @property
    def batch(self) -> int:
        return len(self.rids)


class TimedTokens(list):
    """A request's token list that reports each emission to the recorder.
    The engine appends a new token, overwrites a regenerated one, and
    truncates on rollback; reads are plain list reads."""

    def __init__(self, rid: int, rec: "Recorder"):
        super().__init__()
        self.rid = rid
        self.rec = rec

    def append(self, tok) -> None:
        super().append(tok)
        self.rec.on_token(self.rid, len(self) - 1, int(tok))

    def __setitem__(self, i, tok) -> None:
        super().__setitem__(i, tok)
        if isinstance(i, int):
            self.rec.on_token(self.rid, i % len(self), int(tok))

    def __delitem__(self, i) -> None:
        super().__delitem__(i)
        self.rec.on_truncate(self.rid, len(self))


class Recorder:
    """Host-clock record of one engine run.

    `close` is asked at every pass start and returns True when the window
    is over (it also decides when the window opens); `new_pass(rec, kind)`
    runs at every pass start (the harness starts and stops the profiler
    there).
    """

    def __init__(self, sizes: counters.Sizes, prompt_lens: Dict[int, int],
                 max_new: Dict[int, int], chunk: int, clients: int = 1,
                 fill: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.sizes = sizes
        self.clients = clients
        self.fill = clients if fill is None else fill
        self.plen = dict(prompt_lens)
        self.max_new = dict(max_new)
        self.chunk = chunk
        self.clock = clock
        self.t_start = clock()                      # serving began
        self.t_open: Optional[float] = None         # the window opened
        self.first: Dict[int, List[float]] = {}    # first emission per index
        self.value: Dict[int, List[int]] = {}      # first value per index
        self.ntok: Dict[int, int] = {}             # current token count
        self.logit: Dict[int, Dict[int, float]] = {}  # served token's logit
        self.sampled: Optional[float] = None
        self.regen_changed = 0
        self.completions: List[float] = []
        self.passes: List[Pass] = []
        self.admitted: List[Tuple[float, int]] = []
        self.cursor: Dict[int, int] = {}           # prefill chunk cursor
        self.cur: Optional[Pass] = None
        self.close: Callable[[float], bool] = lambda now: False
        self.new_pass: Optional[Callable[["Recorder", str], None]] = None
        self.annotate: Optional[Callable[[str], None]] = None

    def filled(self) -> bool:
        """The first requests of `fill` clients (all, unless the mix says
        fewer) have emitted their first token: the loop is decoding."""
        return sum(r in self.first for r in range(self.clients)) >= self.fill

    # --- engine.step -------------------------------------------------
    def on_step(self, tag: str) -> None:
        now = self.clock()
        if tag.startswith("decode-r"):
            kind, rid = "decode", int(tag[len("decode-r"):])
        elif tag.startswith("prefill-r"):
            kind, rid = "prefill", int(tag[len("prefill-r"):])
        else:                          # "r<rid>": an admission's first pass
            kind, rid = "admit", int(tag[1:])
        cur = self.cur
        if (cur is None or cur.t1 is not None or kind == "admit"
                or cur.kind != kind or rid in cur.rids):
            if cur is not None and cur.t1 is None:
                cur.t1 = now
            if self.close(now):
                raise WindowClosed()
            if kind == "admit":
                self.admitted.append((now, rid))
            if self.new_pass is not None:
                self.new_pass(self, kind)
            if self.annotate is not None:
                self.annotate("decode_pass" if kind == "decode"
                              else "prefill_pass")
            # the hooks above may take time (starting the profiler): the
            # pass begins after them
            self.cur = cur = Pass("decode" if kind == "decode" else "prefill",
                                  self.clock())
            self.passes.append(cur)
        cur.rids.append(rid)
        cur.flops += self._flops(kind, rid)

    def _flops(self, kind: str, rid: int) -> int:
        s, plen = self.sizes, self.plen[rid]
        if kind == "decode":
            return counters.decode_flops(s, plen + self.ntok.get(rid, 0))
        pos0 = self.cursor.get(rid, 0)
        q = min(self.chunk, plen - pos0)
        self.cursor[rid] = pos0 + q
        return counters.chunk_flops(s, pos0, q, pos0 + q == plen)

    # --- sampler -----------------------------------------------------
    def on_sample(self, start: bool, logit: Optional[float] = None) -> None:
        """Around each sampler call; at its end, `logit` is the logit of
        the token it chose, which the next emission takes."""
        now = self.clock()
        self.sampled = logit
        if not start and self.cur is not None and self.cur.t1 is None:
            self.cur.t1 = now
        if self.annotate is not None:
            self.annotate("sampling" if start else "between_passes")

    # --- tokens --------------------------------------------------------
    def on_token(self, rid: int, i: int, tok: int) -> None:
        now = self.clock()
        first, value = self.first.setdefault(rid, []), \
            self.value.setdefault(rid, [])
        self.ntok[rid] = max(self.ntok.get(rid, 0), i + 1)
        self.logit.setdefault(rid, {})[i] = self.sampled
        if i < len(first):             # regenerated after a rollback
            if value[i] != tok:
                self.regen_changed += 1
            return
        first.append(now)
        value.append(tok)
        if len(first) == self.max_new[rid]:
            self.completions.append(now)

    def on_truncate(self, rid: int, n: int) -> None:
        self.ntok[rid] = n
        if n == 0:
            self.cursor[rid] = 0       # the prefill reruns from the start


# ---------------------------------------------------------------------------
# window arithmetic (pure)
# ---------------------------------------------------------------------------

def tokens_in_window(first: Dict[int, Sequence[float]], t0: float,
                     t1: float) -> int:
    """Output tokens first emitted in [t0, t1]."""
    return sum(1 for ts in first.values() for t in ts if t0 <= t <= t1)


def gaps_in_window(first: Dict[int, Sequence[float]], t0: float,
                   t1: float) -> List[float]:
    """Gaps between consecutive tokens of one request with both tokens in
    [t0, t1]."""
    out = []
    for ts in first.values():
        for a, b in zip(ts, ts[1:]):
            if t0 <= a and b <= t1:
                out.append(b - a)
    return out


def percentile(values: Sequence[float], q: float) -> float:
    if not values:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))


def send_times(completions: Sequence[float], clients: int, n: int,
               t_open: float) -> List[Optional[float]]:
    """Closed loop of `clients`: requests 0..clients-1 are sent when the
    window opens; request k >= clients when the (k-clients+1)-th request
    (in time order) completes.  None: not sent."""
    done = sorted(completions)
    out: List[Optional[float]] = []
    for k in range(n):
        if k < clients:
            out.append(t_open)
        elif k - clients < len(done):
            out.append(done[k - clients])
        else:
            out.append(None)
    return out


def attempted(sends: Sequence[Optional[float]], t1: float) -> int:
    return sum(1 for s in sends if s is not None and s <= t1)
