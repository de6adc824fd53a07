"""Multi-tenant LoRA serving: the adapter bank + registry.

The end state of federated fine-tuning is serving each cohort's (or each
user's) LoRA delta back to the population that trained it.  Per-adapter
engines don't scale — every one would carry its own copy of the shared
base — so the bank keeps N adapters **stacked on a leading adapter axis,
device-resident next to ONE shared base**: the batched decode step gathers
``bank[slot_adapter_ids]`` inside the compiled program and the vmapped
:class:`~fedml_tpu.llm.model.LoRADense` layers run the low-rank matmuls as
slot-batched (grouped) einsums.  Bank *capacity* is static (one compiled
program); *membership* is data — registering, evicting, or re-pointing an
adapter never recompiles anything.

Concurrency contract (the registry is shared between request threads and
the engine's decode thread):

- Row writes go through one jitted donated ``.at[row].set`` under
  ``self.lock``; the engine snapshots ``self.bank`` (and dispatches) under
  the same lock, so a donated-away buffer can never race a dispatch.
- Rows referenced by in-flight requests are **pinned**.  Re-registering a
  pinned name is copy-on-write: the name moves to a fresh row, the old row
  becomes a *zombie* that frees when its pins drain — an in-flight stream
  finishes on exactly the weights it started with.  Evicting a pinned name
  likewise only unroutes it; the row's bytes survive until the last
  reader finishes.
- Row 0 is the reserved **zero adapter** (A = B = 0 — the exact base
  model): requests without an adapter ride the same gathered program, so
  base and personalized traffic share one batch.

Cache mode (``store=``, an :class:`~fedml_tpu.serving.adapter_store
.AdapterStore`): the bank is demoted from *the* registered population to
an N-row HBM cache over the host/disk store.  ``register`` writes
through to the store and only unroutes any stale resident copy — rows
page in lazily on first ``acquire``.  A miss kicks an async store read
(:class:`~fedml_tpu.store.pager.AsyncRowFetcher`) and raises
:class:`AdapterMissError`; the engine parks the request and retries
after the fetch lands.  Residents evict LRU-unpinned under pressure
(their bytes live on in the store), pinned rows never evict, and
``BankFullError`` disappears: registered-adapter count is bounded by the
store, not HBM.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..obs import programs as obs_programs


class BankFullError(RuntimeError):
    """Every non-reserved bank row is registered or still pinned by an
    in-flight request — evict something (or wait for a drain) first.
    (Bank-only registries; cache-mode registries page in/evict instead.)"""


class AdapterMissError(RuntimeError):
    """Cache-mode ``acquire`` miss: the adapter lives in the store but is
    not bank-resident (or every row is pinned).  An async page-in is
    already running — park the request and retry when it lands."""

    def __init__(self, name: str):
        super().__init__(f"adapter {name!r} not bank-resident — "
                         "page-in in flight, requeue the request")
        self.name = name


class _Row:
    __slots__ = ("name", "pins", "zombie", "token")

    def __init__(self):
        self.name: Optional[str] = None
        self.pins = 0
        self.zombie = False
        # identity token, refreshed per registration: prefix-cache keying
        # compares it by ``is`` so KV computed under one adapter version
        # can never serve another (templates/openai_compat.PrefixCache)
        self.token: object = object()


class AdapterRegistry:
    """Name → bank-row routing over a device-resident stacked LoRA bank.

    ``capacity`` counts bank rows *including* the reserved zero row, so a
    capacity-``N`` registry serves up to ``N - 1`` named adapters plus
    base traffic.  All public methods are thread-safe.
    """

    def __init__(self, model, capacity: int = 8, dtype=jnp.float32,
                 store=None):
        if getattr(getattr(model, "cfg", None), "lora_rank", 0) <= 0:
            raise ValueError("AdapterRegistry requires a lora_rank>0 model "
                             "config (LoRADense layers)")
        capacity = int(capacity)
        if capacity < 2:
            raise ValueError(f"capacity={capacity}: need >= 2 (row 0 is the "
                             "reserved zero adapter)")
        self.capacity = capacity
        # cache mode: the bank caches rows of this AdapterStore
        self.store = store
        # eval_shape + zeros, NOT model.init: init would materialize a full
        # base-parameter tree just to read the lora collection's structure
        shapes = jax.eval_shape(
            lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
            jax.random.PRNGKey(0))["lora"]
        self.bank = jax.tree_util.tree_map(
            lambda s: jnp.zeros((capacity,) + s.shape, dtype), shapes)
        self._row_struct = shapes

        @partial(jax.jit, donate_argnums=(0,))
        def set_row(bank, tree, row):
            return jax.tree_util.tree_map(
                lambda b, t: b.at[row].set(t.astype(b.dtype)), bank, tree)

        @jax.jit
        def gather_row(bank, row):
            return jax.tree_util.tree_map(lambda b: b[row], bank)

        self._set_row = set_row
        self._gather_row = gather_row
        # gather_row's registration with obs/programs.py, made at its first
        # launch (None until then)
        self._gather_row_program = None
        self.lock = threading.RLock()
        self._names: Dict[str, int] = {}
        self._rows = [_Row() for _ in range(capacity)]
        self._free: List[int] = list(range(1, capacity))
        self.stats = {"registered": 0, "evicted": 0, "copy_on_write": 0,
                      "rows_reclaimed": 0, "cache_hits": 0,
                      "cache_misses": 0, "cache_evictions": 0}
        # cache-mode state: per-name registration version (stale in-flight
        # fetches are dropped on arrival), LRU clock per row, fetched rows
        # waiting for a free/unpinned slot
        self._ver: Dict[str, int] = {}
        self._lru: Dict[int, int] = {}
        self._lru_clock = 0
        self._pending_install: Dict[str, tuple] = {}
        self._fetcher = None
        self.on_fetch_done = None   # engine wake-up hook (set post-ctor)
        if store is not None:
            from ..store.pager import AsyncRowFetcher
            self._fetcher = AsyncRowFetcher(on_done=self._fetch_done)

    def _fetch_done(self, name: str) -> None:
        cb = self.on_fetch_done
        if cb is not None:
            cb(name)

    def close(self) -> None:
        if self._fetcher is not None:
            self._fetcher.close()
        obs_programs.unregister(self._gather_row_program)
        self._gather_row_program = None

    def program_ops(self) -> Dict[str, Optional[dict]]:
        """``{"gather_row": its instruction -> module map}`` (None before its
        first launch): see ``ContinuousBatchingEngine.program_ops``."""
        return obs_programs.program_ops({"gather_row": self._gather_row_program})

    # -- routing -----------------------------------------------------------
    def names(self) -> List[str]:
        with self.lock:
            if self.store is not None:
                return sorted(set(self._names) | set(self.store.names()))
            return sorted(self._names)

    def __contains__(self, name: str) -> bool:
        with self.lock:
            if self.store is not None and name in self.store:
                return True
            return name in self._names

    def _touch(self, row: int) -> None:
        self._lru_clock += 1
        self._lru[row] = self._lru_clock

    def _install_row(self, name: str, tree) -> Optional[int]:
        """Write a fetched row into the bank (lock held): a free row if
        any, else LRU-evict an unpinned resident.  None when every row is
        pinned (caller re-parks)."""
        if self._free:
            row = self._free.pop()
        else:
            cands = [(self._lru.get(i, 0), i)
                     for i, r in enumerate(self._rows)
                     if i and r.name is not None and r.pins == 0
                     and not r.zombie]
            if not cands:
                return None
            _, row = min(cands)
            old = self._rows[row].name
            del self._names[old]
            self._rows[row].name = None
            self.stats["cache_evictions"] += 1
        self.bank = self._set_row(self.bank, tree, jnp.int32(row))
        r = self._rows[row]
        r.name = name
        r.zombie = False
        r.token = object()
        self._names[name] = row
        self._touch(row)
        return row

    def acquire(self, name: Optional[str]):
        """Resolve ``name`` to ``(row, token)`` and pin the row for the
        lifetime of one request (``None`` → the zero row, never pinned —
        it cannot be evicted or rewritten).  Raises ``KeyError`` for
        unknown names.

        Cache mode: a bank-resident name pins and LRU-touches its row; a
        store-only name kicks an async page-in and raises
        :class:`AdapterMissError` (requeue and retry)."""
        with self.lock:
            if name is None:
                return 0, self._rows[0].token
            row = self._names.get(name)
            if row is not None:
                self._rows[row].pins += 1
                if self.store is not None:
                    self._touch(row)
                    self.stats["cache_hits"] += 1
                return row, self._rows[row].token
            if self.store is None:
                raise KeyError(
                    f"unknown adapter {name!r}; have {sorted(self._names)}")
            # fetched already? install now (engine thread holds the lock,
            # so the donated bank write cannot race a dispatch snapshot)
            pending = self._pending_install.pop(name, None)
            if pending is None:
                ok, val = self._fetcher.take(name)
                if ok:
                    pending = val
            if pending is not None:
                ver, tree = pending
                if ver == self._ver.get(name):
                    row = self._install_row(name, tree)
                    if row is not None:
                        self._rows[row].pins += 1
                        self.stats["cache_hits"] += 1
                        return row, self._rows[row].token
                    # every row pinned right now — hold the bytes, retry
                    self._pending_install[name] = pending
                    raise AdapterMissError(name)
                # stale version fetched mid-re-register: refetch below
            if name not in self.store:
                raise KeyError(
                    f"unknown adapter {name!r}; have {self.names()}")
            ver = self._ver.get(name)
            store = self.store
            if self._fetcher.request(
                    name, lambda: (ver, store.get(name))):
                self.stats["cache_misses"] += 1
            raise AdapterMissError(name)

    def release(self, row: int) -> None:
        """Drop one pin; a zombie row whose pins drain returns to the free
        list."""
        if row == 0:
            return
        with self.lock:
            r = self._rows[row]
            r.pins = max(r.pins - 1, 0)
            if r.zombie and r.pins == 0:
                r.zombie = False
                self._free.append(row)
                self.stats["rows_reclaimed"] += 1

    def lora_for_row(self, row: int):
        """Gathered single-adapter tree for one row (prefill-time use)."""
        with self.lock:
            row = jnp.int32(row)
            if self._gather_row_program is None:
                self._gather_row_program = obs_programs.register(
                    "gather_row", self._gather_row, (self.bank, row))
            return self._gather_row(self.bank, row)

    # -- membership --------------------------------------------------------
    def _check_tree(self, lora_tree) -> None:
        got_def = jax.tree_util.tree_structure(lora_tree)
        want_def = jax.tree_util.tree_structure(self._row_struct)
        if got_def != want_def:
            raise ValueError(
                "lora tree does not match the bank's row structure "
                f"(model lora config mismatch): got {got_def}, "
                f"want {want_def}")
        for got, want in zip(jax.tree_util.tree_leaves(lora_tree),
                             jax.tree_util.tree_leaves(self._row_struct)):
            if tuple(got.shape) != tuple(want.shape):
                raise ValueError(
                    "lora leaf shape mismatch vs the bank row: got "
                    f"{tuple(got.shape)}, want {tuple(want.shape)} "
                    "(model lora_rank/config mismatch)")

    def register(self, name: str, lora_tree) -> int:
        """Write ``lora_tree`` into a bank row and route ``name`` to it.

        A re-register of an *unpinned* name rewrites its row in place; a
        *pinned* name moves to a fresh row (copy-on-write) so in-flight
        requests keep decoding against the weights they started with.
        Raises :class:`BankFullError` when no row is free.

        Cache mode writes through to the STORE, not the bank: any stale
        resident copy is unrouted (zombie while pinned — in-flight
        streams finish on the weights they started with) and the new
        version pages into a row lazily on first ``acquire``.  Returns
        -1 (no resident row yet); never raises ``BankFullError``."""
        name = str(name)
        self._check_tree(lora_tree)
        if self.store is not None:
            with self.lock:
                self._ver[name] = self._ver.get(name, 0) + 1
                self.store.put(name, lora_tree)
                self._pending_install.pop(name, None)
                row = self._names.pop(name, None)
                if row is not None:
                    r = self._rows[row]
                    r.name = None
                    if r.pins > 0:
                        r.zombie = True
                        self.stats["copy_on_write"] += 1
                    else:
                        self._free.append(row)
                self.stats["registered"] += 1
                return -1
        with self.lock:
            row = self._names.get(name)
            if row is not None and self._rows[row].pins > 0:
                # copy-on-write: the old row keeps serving its readers
                self._rows[row].zombie = True
                self._rows[row].name = None
                self.stats["copy_on_write"] += 1
                row = None
            if row is None:
                if not self._free:
                    raise BankFullError(
                        f"adapter bank full ({self.capacity - 1} rows; "
                        f"registered={sorted(self._names)}, zombies="
                        f"{sum(r.zombie for r in self._rows)}) — evict an "
                        "adapter or wait for in-flight requests to drain")
                row = self._free.pop()
            self.bank = self._set_row(self.bank, lora_tree, jnp.int32(row))
            r = self._rows[row]
            r.name = name
            r.zombie = False
            r.token = object()
            self._names[name] = row
            self.stats["registered"] += 1
            return row

    def evict(self, name: str) -> None:
        """Unroute ``name``.  New requests for it fail immediately; a row
        still pinned by in-flight requests survives as a zombie until they
        drain, then frees.  Cache mode also drops the store copy (and
        invalidates any in-flight page-in of it)."""
        name = str(name)
        with self.lock:
            row = self._names.pop(name, None)
            if self.store is not None:
                known = row is not None or name in self.store
                if not known:
                    raise KeyError(f"unknown adapter {name!r}")
                self.store.remove(name)
                self._ver[name] = self._ver.get(name, 0) + 1
                self._pending_install.pop(name, None)
                self.stats["evicted"] += 1
                if row is None:
                    return
            elif row is None:
                raise KeyError(f"unknown adapter {name!r}")
            else:
                self.stats["evicted"] += 1
            r = self._rows[row]
            r.name = None
            if r.pins > 0:
                r.zombie = True
            else:
                self._free.append(row)

    # -- federated handoff -------------------------------------------------
    def register_from_checkpoint(self, name: str, directory: str,
                                 round_idx: Optional[int] = None,
                                 member: Optional[int] = None) -> int:
        """Register a LoRA delta straight out of a federated orbax
        checkpoint — a fine-tune run's output becomes servable without a
        restart.  The saved state may be the bare lora tree, any dict
        carrying a ``"lora"`` key, or a population-stacked run (pass
        ``member`` to extract one experiment via
        :func:`fedml_tpu.core.federated.population_member`)."""
        from ..core.checkpoint import RoundCheckpointer
        ckpt = RoundCheckpointer(directory)
        try:
            state = ckpt.restore_state(round_idx)
        finally:
            ckpt.close()
        if state is None:
            raise FileNotFoundError(
                f"no checkpoint round in {directory!r}")
        tree = state["lora"] if isinstance(state, dict) and "lora" in state \
            else state
        if member is not None:
            from ..core.federated import population_member
            tree = population_member(tree, int(member))
        return self.register(name, tree)


__all__ = ["AdapterRegistry", "AdapterMissError", "BankFullError"]
