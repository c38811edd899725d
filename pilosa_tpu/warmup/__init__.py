"""Warm-start subsystem (docs/warmup.md): persistent compile cache,
durable signature corpus, and AOT warmup to READY.

A restart today pays the full trace+compile bill per program signature;
the reference engine just reopens mmap'd fragments.  This package earns
the same property for an XLA-lowered engine in three layers:

* ``compile_cache`` — jax's on-disk persistent compilation cache on one
  fixed directory (or the environment's), size-bounded with LRU pruning;
* ``corpus`` — a CRC-framed durable log of what this process compiles
  (signature, shape fingerprint, params schema/template, traffic);
* ``replayer`` — the boot-time coordinator that replays the top-N
  corpus queries through the real compile paths before READY.
"""

from .compile_cache import cache_stats, configure, owned, prune
from .corpus import CorpusRecorder, SignatureCorpus, top_n
from .replayer import (PHASE_COLD, PHASE_READY, PHASE_WARMING,
                       WarmupCoordinator)

__all__ = [
    "cache_stats", "configure", "owned", "prune",
    "CorpusRecorder", "SignatureCorpus", "top_n",
    "PHASE_COLD", "PHASE_READY", "PHASE_WARMING", "WarmupCoordinator",
]
