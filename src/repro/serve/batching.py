"""Bucketed micro-batching for the ANN serve path.

Serving traffic arrives as ragged request batches (1 query here, 17 there).
Every distinct batch shape is a fresh XLA compilation, so a naive serve loop
spends its first minutes tracing instead of answering. This module keeps the
jit cache hot under mixed batch sizes:

  * ``pow2_buckets`` — the allowed batch shapes (powers of two up to the
    configured maximum);
  * ``BucketedSearch`` — pads every request batch up to its bucket, runs the
    underlying search step, slices the padding back off. After ``warmup``
    (one compile per bucket at startup) no request ever triggers a trace;
  * ``MicroBatchQueue`` — accumulates requests for up to ``window_s``
    seconds (or until the largest bucket fills), then serves them as one
    padded batch and scatters results back per ticket.

Results are exactly those of the unbatched search: padding rows are sliced
off before anything is returned, and the per-query traversal is independent
of its batch neighbors (beam_search lanes never interact).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.spans import span


def pow2_buckets(max_batch: int, min_bucket: int = 1) -> Tuple[int, ...]:
    """Power-of-two bucket sizes covering [1, max_batch]."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets = []
    b = max(1, min_bucket)
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(b)            # first power of two >= max_batch
    return tuple(buckets)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket that fits ``n`` queries."""
    for b in sorted(buckets):
        if n <= b:
            return b
    raise ValueError(f"batch of {n} exceeds largest bucket {max(buckets)}")


class BucketedSearch:
    """Pad request batches to fixed bucket shapes around any search step.

    ``search_fn(queries) -> (dists, ids)`` is the wrapped step (e.g. the
    closure from ``serve_step.ann_search_step``). Padding queries are copies
    of the batch's first row — always in-distribution, sliced off on return.
    ``dispatched`` records the padded batch size of every underlying call,
    so tests (and ops dashboards) can verify the shape set stays equal to
    the warmed bucket set.
    """

    def __init__(self, search_fn: Callable, buckets: Sequence[int]):
        if not buckets:
            raise ValueError("need at least one bucket")
        self.search_fn = search_fn
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.dispatched: List[int] = []

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def warmup(self, dim: int, dtype=jnp.float32) -> None:
        """Compile every bucket shape up front (server start, not first hit)."""
        for b in self.buckets:
            out = self.search_fn(jnp.zeros((b, dim), dtype))
            jax.block_until_ready(out)
            self.dispatched.append(b)

    def __call__(self, queries: jax.Array):
        n = queries.shape[0]
        if n > self.max_batch:          # oversized: serve in max-bucket runs
            parts = [self(queries[s:s + self.max_batch])
                     for s in range(0, n, self.max_batch)]
            return (jnp.concatenate([d for d, _ in parts]),
                    jnp.concatenate([i for _, i in parts]))
        b = bucket_for(n, self.buckets)
        with span("search.call", bucket=b):
            if n < b:
                with span("bucket.pad"):
                    pad = jnp.broadcast_to(queries[:1],
                                           (b - n,) + queries.shape[1:])
                    padded = jnp.concatenate([queries, pad], axis=0)
            else:
                padded = queries
            self.dispatched.append(b)
            d, i = self.search_fn(padded)
            with span("bucket.slice"):
                return d[:n], i[:n]

    def padded_size(self, n: int) -> int:
        """Rows dispatched for a batch of ``n``: its max-bucket runs and
        the bucket of the rest."""
        full, rest = divmod(n, self.max_batch)
        return full * self.max_batch + (bucket_for(rest, self.buckets)
                                        if rest else 0)


class MicroBatchQueue:
    """Accumulate requests, serve them as one bucketed batch per flush.

    Synchronous single-owner queue (the serve loop owns it; a real deployment
    would put it behind an RPC thread): ``submit`` returns a ticket,
    ``flush`` answers every pending ticket, ``take(ticket)`` pops the answer
    (popping is what keeps ``results`` bounded on a long-running server).
    ``maybe_flush`` flushes when the batching window has elapsed or the
    largest bucket is full — the latency/throughput trade the window knob
    controls.

    Zero-lost-tickets contract: EVERY submitted ticket is answered — with
    (dists, ids) on success, or with a typed
    ``serve.resilience.SearchFailure`` when the flush's search fails
    (retried once, then failed) or the queue sheds under ``max_queue``
    backpressure. The pending list is cleared before the search runs, so
    an exception can never strand tickets in limbo (the pre-hardening
    bug: a raise left ``_pending`` populated and ``results`` empty —
    callers of ``take`` hung or KeyError'd forever).

    Per-query latency (submit -> flush completion, one sample per served
    query) and batch occupancy (real rows / dispatched padded rows per
    flush) are recorded as they happen; ``latency_stats()`` reduces them
    to the p50/p99/mean the serve loop reports, plus the failure
    accounting (``errors`` tickets failed, ``retries`` flush re-attempts,
    ``shed`` tickets rejected at submit).
    """

    def __init__(self, search: BucketedSearch, window_s: float = 0.002,
                 flush_retries: int = 1,
                 max_queue: Optional[int] = None):
        self.search = search
        self.window_s = window_s
        self.flush_retries = flush_retries
        self.max_queue = max_queue       # pending-row cap; None = unbounded
        self._pending: List[Tuple[int, np.ndarray, float]] = []
        self._pending_rows = 0
        self._oldest: Optional[float] = None
        self._next_ticket = 0
        self.results: Dict[int, object] = {}
        self._latency_s: List[float] = []     # one sample per served query
        self._occupancy: List[float] = []     # rows / padded rows per flush
        self.flushes = 0
        self.errors = 0                  # tickets answered with a failure
        self.retries = 0                 # flush search re-attempts
        self.shed = 0                    # tickets rejected at submit

    def submit(self, queries) -> int:
        """Enqueue a (n, D) request; returns a ticket for ``results``.

        Under ``max_queue`` backpressure (pending rows would exceed it
        even after a flush) the ticket is answered IMMEDIATELY with a
        ``SearchFailure(error_type="QueueFull")`` — shed, not lost.
        """
        from repro.serve.resilience import SearchFailure
        q = np.atleast_2d(np.asarray(queries))
        if self._pending_rows + q.shape[0] > self.search.max_batch:
            self.flush()
        ticket = self._next_ticket
        self._next_ticket += 1
        if (self.max_queue is not None
                and self._pending_rows + q.shape[0] > self.max_queue):
            self.shed += 1
            self.results[ticket] = SearchFailure(
                error=f"queue full ({self._pending_rows} rows pending, "
                      f"max_queue={self.max_queue})",
                error_type="QueueFull", attempts=0)
            return ticket
        self._pending.append((ticket, q, time.perf_counter()))
        self._pending_rows += q.shape[0]
        if self._oldest is None:
            self._oldest = time.perf_counter()
        return ticket

    def take(self, ticket: int):
        """Pop a flushed ticket's answer — (dists, ids) or a
        ``SearchFailure`` — once, keeping memory flat."""
        return self.results.pop(ticket)

    def maybe_flush(self) -> bool:
        """Flush if the window elapsed or the largest bucket is full."""
        if not self._pending:
            return False
        full = self._pending_rows >= self.search.max_batch
        due = (time.perf_counter() - self._oldest) >= self.window_s
        if full or due:
            self.flush()
            return True
        return False

    def flush(self) -> None:
        if not self._pending:
            return
        # clear queue state FIRST: whatever happens below, these tickets
        # are this flush's to answer and the queue is ready for new work
        pending, self._pending = self._pending, []
        rows, self._pending_rows = self._pending_rows, 0
        self._oldest = None
        padded_size = getattr(self.search, "padded_size", None)
        with span("queue.flush", flush=self.flushes, rows=rows,
                  padded=padded_size(rows) if padded_size else rows):
            self._serve(pending)

    def _serve(self, pending) -> None:
        from repro.serve.resilience import SearchFailure
        with span("queue.h2d", bytes=sum(q.nbytes for _, q, _ in pending)):
            batch = jnp.asarray(np.concatenate([q for _, q, _ in pending],
                                               axis=0))
        n_disp = len(getattr(self.search, "dispatched", ()))
        err: Optional[BaseException] = None
        attempts = 0
        for attempt in range(self.flush_retries + 1):
            attempts = attempt + 1
            try:
                d, i = self.search(batch)
                with span("queue.d2h"):
                    d, i = np.asarray(d), np.asarray(i)
                err = None
                break
            except Exception as e:
                err = e
                if attempt < self.flush_retries:
                    self.retries += 1
        done = time.perf_counter()
        self.flushes += 1
        if err is not None:
            # answer every ticket with the typed failure — none lost
            failure = SearchFailure(error=str(err),
                                    error_type=type(err).__name__,
                                    attempts=attempts)
            for ticket, _, _ in pending:
                self.results[ticket] = failure
                self.errors += 1
            return
        padded = sum(getattr(self.search, "dispatched", ())[n_disp:])
        if padded:
            self._occupancy.append(batch.shape[0] / padded)
        with span("queue.scatter"):
            row = 0
            for ticket, q, submitted in pending:
                n = q.shape[0]
                self.results[ticket] = (d[row:row + n], i[row:row + n])
                self._latency_s.extend([done - submitted] * n)
                row += n

    def latency_stats(self) -> dict:
        """Serving distribution so far: per-query latency percentiles (ms),
        mean batch occupancy (1.0 = every dispatched row was a real query;
        below that is bucket-padding overhead), and failure accounting
        (errors / retries / shed)."""
        lat = np.asarray(self._latency_s, np.float64) * 1e3
        return {
            "served": int(lat.size),
            "flushes": self.flushes,
            "p50_ms": float(np.percentile(lat, 50)) if lat.size else 0.0,
            "p99_ms": float(np.percentile(lat, 99)) if lat.size else 0.0,
            "mean_ms": float(lat.mean()) if lat.size else 0.0,
            "mean_occupancy": float(np.mean(self._occupancy))
            if self._occupancy else 0.0,
            "errors": self.errors,
            "retries": self.retries,
            "shed": self.shed,
        }
