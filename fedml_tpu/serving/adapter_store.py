"""AdapterStore — host/disk backing store for the serving adapter cache.

The HBM adapter bank (``serving/adapters.py``) used to BE the registered
population: ``adapter_slots=N`` meant at most N−1 named adapters, ever.
This store demotes the bank to an N-row cache: every registered adapter's
LoRA tree lives here as one row of a :class:`ClientStateStore` — the same
sparse hash-paged host table (with optional LRU ``.npz`` spill past
``max_resident_pages``) that scaled per-client training state past HBM in
the fedstore work — and the registry pages rows in on cache miss.
Registered-adapter count is now bounded by host RAM / disk, not HBM
(the bank's bytes stay flat from 32 to 10k registered adapters:
``tests/test_serving_paged.py``).

Thread-safety: the name→row-id map and the underlying store carry their
own locks; ``put``/``get`` may be called from HTTP registration threads
and the registry's async fetch worker concurrently.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..store.clientstore import ClientStateStore

Pytree = Any


class AdapterStore:
    """Named LoRA-tree rows over a :class:`ClientStateStore`.

    ``model`` supplies the row template (the lora collection's
    shapes/dtypes via ``eval_shape`` — nothing is materialized);
    ``registered`` bounds the id space (ids are assigned to names in
    registration order and never reused).  ``spill_dir`` +
    ``max_resident_pages`` bound host RSS by spilling cold pages to disk
    (``adapter_store_dir`` on the engine/server ctor).
    """

    def __init__(self, model, registered: int = 16384,
                 page_size: int = 64, max_resident_pages: int = 0,
                 spill_dir: Optional[str] = None):
        shapes = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))
        if "lora" not in shapes:
            raise ValueError("model has no 'lora' collection "
                             "(lora_rank=0?) — nothing to store")
        template = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), shapes["lora"])
        self._store = ClientStateStore(
            template, registered=int(registered), page_size=page_size,
            max_resident_pages=max_resident_pages, spill_dir=spill_dir)
        self._ids: Dict[str, int] = {}
        self._next = 0
        self._lock = threading.RLock()

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._ids

    def names(self) -> List[str]:
        with self._lock:
            return list(self._ids)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ids)

    def put(self, name: str, tree: Pytree) -> None:
        """Write (or overwrite) ``name``'s row.  Host copies only — the
        caller's device arrays are materialized here, off the bank."""
        with self._lock:
            rid = self._ids.get(name)
            if rid is None:
                if self._next >= self._store.registered:
                    raise RuntimeError(
                        f"adapter store full ({self._store.registered} "
                        "ids) — raise `registered`")
                rid = self._next
                self._next += 1
                self._ids[name] = rid
        rows = jax.tree_util.tree_map(
            lambda l: np.asarray(l)[None], tree)
        self._store.scatter(np.array([rid], np.int64), rows)

    def get(self, name: str) -> Pytree:
        """Read ``name``'s row (KeyError for unknown names); may hit the
        disk spill path — callers on a latency-sensitive thread should go
        through the registry's async fetcher instead."""
        with self._lock:
            rid = self._ids[name]
        rows = self._store.gather(np.array([rid], np.int64))
        return jax.tree_util.tree_map(lambda l: l[0], rows)

    def remove(self, name: str) -> None:
        """Drop the name→row routing (the row itself stays; ids are not
        reused, matching the registry's evict-then-reregister flow)."""
        with self._lock:
            self._ids.pop(name, None)

    def stats(self) -> Dict[str, int]:
        s = dict(self._store.stats())
        with self._lock:
            s["registered_names"] = len(self._ids)
        s["row_nbytes"] = self._store.row_nbytes
        return s
