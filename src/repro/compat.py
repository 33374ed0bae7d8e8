"""One import point for the jax APIs the model and kernel code share.

The repository targets the installed toolchain only (jax/jaxlib 0.9.0,
pinned in ``requirements.txt``): ``shard_map`` comes from the top-level
namespace and takes ``check_vma``; the segment reductions come from
``jax.ops``.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def segment_sum(data, segment_ids, num_segments):
    """sum of ``data`` rows per segment id -> [num_segments, ...]."""
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_max(data, segment_ids, num_segments):
    """max of ``data`` rows per segment id; empty segments -> -inf."""
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)
