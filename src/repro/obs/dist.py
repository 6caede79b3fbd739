"""Cross-host aggregation of registry snapshots.

The same wire discipline as the router's stat gathers
(:mod:`repro.deploy.router`): tiny fixed-size collectives, every rank
calls together, any rank can report the merged result. A snapshot is
variable-size JSON, so it rides as a two-phase gather — lengths first
(the int32-halves trick of ``allgather_i64``), then zero-padded uint8
payloads — both bounded because histogram reservoirs are bounded.

jax is imported lazily: ``repro.obs`` stays importable (and its
``--selftest`` able to pin XLA flags) before jax initializes.
"""
from __future__ import annotations

import json
from typing import List

import numpy as np


def allgather_snapshots(snapshot: dict) -> List[dict]:
    """Allgather one registry snapshot per host → all hosts' snapshots
    (collective: every rank must call together). Single-process: the
    identity."""
    import jax

    if jax.process_count() == 1:
        return [snapshot]
    from jax.experimental import multihost_utils

    data = np.frombuffer(
        json.dumps(snapshot, sort_keys=True).encode(), np.uint8)
    sizes = np.asarray(multihost_utils.process_allgather(
        np.asarray([data.size], np.int32))).ravel()
    buf = np.zeros((int(sizes.max()),), np.uint8) if sizes.max() \
        else np.zeros((1,), np.uint8)
    buf[:data.size] = data
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    gathered = gathered.reshape(len(sizes), -1)
    return [json.loads(bytes(gathered[i, :sizes[i]]).decode())
            for i in range(len(sizes))]
