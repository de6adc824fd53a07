"""Round-level checkpoint/resume — first-class, unlike the reference.

SURVEY §5: the reference has no round checkpointing in the core FL loop
(models persist only as S3 artifacts, ``core/mlops/__init__.py:532``); the
LLM path leans on HF Trainer checkpoints.  Here the WHOLE server state — a
single pytree (``ServerState``: params, server-optimizer moments, SCAFFOLD
c, FedDyn h, round counter) — checkpoints atomically with orbax, including
sharded arrays on a mesh, plus the host-side per-client state dict.
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp


class RoundCheckpointer:
    def __init__(self, directory: str, max_to_keep: int = 3):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                                 create=True),
            # pre-register the standard handler so a FRESH process can read
            # item_metadata() of an existing checkpoint before any
            # save/restore (the legacy dense-table -> sparse-store
            # migration rebuilds its restore template from metadata)
            item_handlers=ocp.StandardCheckpointHandler(),
        )

    @staticmethod
    def _is_legacy_dict(client_state) -> bool:
        """Legacy layout: a host dict keyed by int client id.  The current
        engines keep per-client state as a device-resident dense table
        (one pytree, rows indexed by client id) instead."""
        return isinstance(client_state, dict) and (
            not client_state
            or all(isinstance(k, int) for k in client_state))

    @staticmethod
    def _is_store(client_state) -> bool:
        """Paged sparse store (fedml_tpu/store): duck-typed so this module
        never imports the store package."""
        return (hasattr(client_state, "to_checkpoint")
                and hasattr(client_state, "load_checkpoint"))

    def _store_path(self, step: int) -> str:
        return os.path.join(self.directory, f"store_{int(step)}.npz")

    def _prune_store_sidecars(self):
        """Drop sparse-store sidecars whose orbax step was retired by
        max_to_keep, so the directory's footprint tracks the manager's."""
        import glob
        keep = {int(s) for s in (self.mngr.all_steps() or [])}
        for p in glob.glob(os.path.join(self.directory, "store_*.npz")):
            try:
                step = int(os.path.basename(p)[len("store_"):-len(".npz")])
            except ValueError:
                continue
            if step not in keep:
                os.remove(p)

    def _composite(self, state: Any, client_state) -> dict:
        composite = {"state": state}
        if client_state is None:
            return composite
        if self._is_legacy_dict(client_state):
            if client_state:
                composite["client_state"] = {
                    str(k): v for k, v in client_state.items()}
        else:
            composite["client_table"] = client_state
        return composite

    def save(self, round_idx: int, state: Any,
             client_state: Optional[Any] = None, force: bool = False):
        """state: any pytree (ServerState); client_state: the dense
        per-client state table (pytree with a leading client-row axis —
        orbax persists its sharding like any other leaf), a
        :class:`~fedml_tpu.store.ClientStateStore` (saved SPARSE — only
        touched rows — as an ``.npz`` sidecar next to the orbax step), or
        the legacy host dict of per-client pytrees."""
        store = client_state if self._is_store(client_state) else None
        if store is not None:
            client_state = None
        self.mngr.save(round_idx,
                       args=ocp.args.StandardSave(
                           self._composite(state, client_state)),
                       force=force)
        self.mngr.wait_until_finished()
        if store is not None:
            np.savez(self._store_path(round_idx), **store.to_checkpoint())
        self._prune_store_sidecars()

    def latest_round(self) -> Optional[int]:
        return self.mngr.latest_step()

    def restore(self, round_idx: Optional[int] = None,
                template: Optional[Any] = None):
        """Returns (state, client_state) or None if no checkpoint;
        ``client_state`` is the dense table pytree when one was saved,
        else the legacy int-keyed dict (``{}`` when absent).  When the
        template carries a sparse store, the store is loaded IN PLACE and
        returned — from its own sparse sidecar, or by migrating a legacy
        dense ``client_table`` / host-dict checkpoint into it."""
        step = round_idx if round_idx is not None else self.mngr.latest_step()
        if step is None:
            return None
        if template is not None and self._is_store(template[1]):
            return self._restore_into_store(step, template[0], template[1])
        if template is not None:
            restored = self.mngr.restore(
                step, args=ocp.args.StandardRestore(
                    self._composite(template[0], template[1])))
        else:
            restored = self.mngr.restore(step)
        if "client_table" in restored:
            return restored["state"], restored["client_table"]
        client_state = {
            int(k): v for k, v in restored.get("client_state", {}).items()}
        return restored["state"], client_state

    def restore_state(self, round_idx: Optional[int] = None):
        """Restore ONLY the saved state pytree, with the template rebuilt
        from the step's orbax metadata (shapes/dtypes) — so a consumer
        that was not the writer (e.g. the serving
        :class:`~fedml_tpu.serving.adapters.AdapterRegistry` pulling a
        LoRA delta, possibly population-stacked, out of a fine-tune run)
        never has to materialize or even know the full state structure.
        Returns ``None`` when no checkpoint round exists."""
        step = round_idx if round_idx is not None else self.mngr.latest_step()
        if step is None:
            return None
        meta = self._item_tree(step)
        if "state" not in meta:
            return None
        template = jax.tree_util.tree_map(
            lambda m: np.zeros(m.shape, m.dtype), meta["state"])
        restored = self.mngr.restore(
            step, args=ocp.args.StandardRestore({"state": template}))
        return restored["state"]

    def _item_tree(self, step: int) -> dict:
        """The saved composite's structure with an ``ArrayMetadata``
        (shape, dtype) at every leaf — what a reader that was not the
        writer builds its restore template from."""
        return self.mngr.item_metadata(step).tree

    def _restore_into_store(self, step: int, state_template: Any, store):
        """Store-backed restore: the ServerState comes from orbax against
        its template; the per-client rows come from the sparse ``.npz``
        sidecar, or — legacy checkpoints — from the saved dense
        ``client_table`` / host-dict item, rebuilt from the step's orbax
        METADATA (shapes/dtypes) so the caller never has to materialize a
        dense template itself."""
        sidecar = self._store_path(step)
        comp = {"state": state_template}
        legacy_key = None
        if not os.path.exists(sidecar):
            meta = self._item_tree(step)
            for key in ("client_table", "client_state"):
                if key in meta:
                    legacy_key = key
                    comp[key] = jax.tree_util.tree_map(
                        lambda m: np.zeros(m.shape, m.dtype), meta[key])
                    break
        restored = self.mngr.restore(
            step, args=ocp.args.StandardRestore(comp))
        if os.path.exists(sidecar):
            with np.load(sidecar) as z:
                store.load_checkpoint({k: z[k] for k in z.files})
        elif legacy_key == "client_table":
            store.load_dense(restored["client_table"])
        elif legacy_key == "client_state":
            for cid, row in restored["client_state"].items():
                store.scatter(
                    np.asarray([int(cid)], np.int64),
                    jax.tree_util.tree_map(lambda x: np.asarray(x)[None],
                                           row))
        return restored["state"], store

    def close(self):
        self.mngr.close()


class WireCheckpointer:
    """fedwire-unified round checkpoints (``args.checkpoint_codec="wire"``,
    docs/WIRE.md): each round is ONE wire-fp32 payload (the same
    :class:`~fedml_tpu.core.wire.WireCodec` that frames wire messages,
    bitwise at fp32) msgpack'd to ``wire_<round>.msgpack`` with an atomic
    tmp→rename, plus the same sparse-store ``.npz`` sidecar the orbax
    checkpointer writes.  Same save/restore/latest_round/close surface as
    :class:`RoundCheckpointer`, so ``FedAvgAPI`` selects by args alone.

    Trade-off vs orbax: single-host, no sharded-array layout — but the
    checkpoint bytes ARE wire bytes, so state-sync after resume and the
    WAL ``state_digest`` verify against the identical encoding.
    """

    def __init__(self, directory: str, max_to_keep: int = 3):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.max_to_keep = int(max_to_keep)

    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"wire_{int(step)}.msgpack")

    def _store_path(self, step: int) -> str:
        return os.path.join(self.directory, f"store_{int(step)}.npz")

    def _steps(self):
        import glob
        out = []
        for p in glob.glob(os.path.join(self.directory, "wire_*.msgpack")):
            try:
                out.append(int(
                    os.path.basename(p)[len("wire_"):-len(".msgpack")]))
            except ValueError:
                continue
        return sorted(out)

    def _prune(self):
        steps = self._steps()
        for step in steps[:-self.max_to_keep] if self.max_to_keep else []:
            os.remove(self._path(step))
        keep = set(self._steps())
        import glob
        for p in glob.glob(os.path.join(self.directory, "store_*.npz")):
            try:
                step = int(os.path.basename(p)[len("store_"):-len(".npz")])
            except ValueError:
                continue
            if step not in keep:
                os.remove(p)

    def save(self, round_idx: int, state: Any,
             client_state: Optional[Any] = None, force: bool = False):
        import flax.serialization as fser

        from .distributed.communication.message import encode_tree
        from .wire import WireCodec

        comp = {"state": fser.to_state_dict(state)}
        store = (client_state
                 if RoundCheckpointer._is_store(client_state) else None)
        if client_state is not None and store is None \
                and not RoundCheckpointer._is_legacy_dict(client_state):
            comp["client_table"] = fser.to_state_dict(client_state)
        payload, _ = WireCodec("fp32").encode(comp)
        path = self._path(round_idx)
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(encode_tree(payload))
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        if store is not None:
            np.savez(self._store_path(round_idx), **store.to_checkpoint())
        self._prune()

    def latest_round(self) -> Optional[int]:
        steps = self._steps()
        return steps[-1] if steps else None

    def _load(self, step: int) -> dict:
        from .distributed.communication.message import decode_tree
        from .wire import WireCodec
        with open(self._path(step), "rb") as fh:
            return WireCodec.decode(decode_tree(fh.read()))

    def restore(self, round_idx: Optional[int] = None,
                template: Optional[Any] = None):
        import flax.serialization as fser
        step = round_idx if round_idx is not None else self.latest_round()
        if step is None:
            return None
        comp = self._load(step)
        state = comp["state"]
        client = comp.get("client_table")
        if template is not None:
            state = fser.from_state_dict(template[0], state)
            if RoundCheckpointer._is_store(template[1]):
                store = template[1]
                sidecar = self._store_path(step)
                if os.path.exists(sidecar):
                    with np.load(sidecar) as z:
                        store.load_checkpoint({k: z[k] for k in z.files})
                elif client is not None:
                    store.load_dense(client)
                return state, store
            if template[1] is not None and client is not None:
                client = fser.from_state_dict(template[1], client)
        return state, client if client is not None else {}

    def restore_state(self, round_idx: Optional[int] = None):
        """The saved state as its NESTED STATE DICT (wire payloads are
        self-describing, so no template/metadata is needed — but the
        dataclass wrapper is the caller's to rebuild)."""
        step = round_idx if round_idx is not None else self.latest_round()
        if step is None:
            return None
        return self._load(step)["state"]

    def close(self):
        pass
