"""Named host spans of the serve path, on the profiler's clock.

Each stage of a flush runs inside ``span(name)``, a
``jax.profiler.TraceAnnotation``: under a running profiler it is a host
event in the same trace as the device's ops, so a stretch in which the
device ran nothing can be put down to the stage the host was in. With no
profiler running a span costs about a microsecond to enter and leave, so
the spans are always on.

Nesting, outermost first::

    queue.flush                 MicroBatchQueue.flush (args: flush, rows, padded)
      queue.h2d                 the request rows joined and put on the device
      search.call               BucketedSearch.__call__ (args: bucket)
        bucket.pad              padding rows up to the bucket, where n < bucket
        index.search            the index's search
          search.project        graph: PCA projection of the queries
          search.entries        graph: entry-point selection
          search.traverse       graph: the beam search
            search.lut          quantized graph: the per-query ADC table
            search.rerank       quantized graph: the exact float32 tail
          search.ids            graph: internal ids mapped to the corpus's
          search.scan           flat: the chunked scan and top-k
        bucket.slice            the padding rows cut off the answers
      queue.d2h                 wait for the answers and copy them to the host
      queue.scatter             answers handed to their tickets
    index.stats                 TunedGraphIndex.search_stats, after a flush

``SPANS`` names every span the program emits, so a trace reader can pick
them out of the host's events.
"""
from __future__ import annotations

import jax

SPANS = ("queue.flush", "queue.h2d", "search.call", "bucket.pad",
         "index.search", "search.project", "search.entries",
         "search.traverse", "search.lut", "search.rerank", "search.ids",
         "search.scan", "bucket.slice", "queue.d2h", "queue.scatter",
         "index.stats")


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` (one of ``SPANS``), with ``args`` as its
    event's arguments in the trace."""
    return jax.profiler.TraceAnnotation(name, **args)
