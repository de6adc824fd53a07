"""Edge federation driver — server side of the shared-directory protocol
spoken by the native edge client binary (``native/edge_client_main.cpp``,
the ``main_MNN_train.cpp`` analog).

The reference drives Android clients over MQTT+S3-MNN
(``cross_device/server_mnn/fedml_aggregator.py:17`` aggregates returned MNN
model files; the protocol is exercised from Python by
``python/tests/android_protocol_test/test_protocol.py``).  Here the control
plane is task/done files and the data plane is edge bundles in a shared
directory — same split, broker-less, NFS/GCS-fuse friendly.

Per round R the server publishes ``round_R/global.fteb`` + ``task.txt``,
waits for every client's ``client_C.fteb`` + ``client_C.done``, aggregates
with sample-count weights (FedAvg semantics of
``ml/aggregator/agg_operator.py``), and finally writes ``finish.txt``.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional

import numpy as np

from ..native.edge_bundle import read_bundle, write_bundle

log = logging.getLogger(__name__)


_I64_MAGIC = 0x38495446  # "FTI8" — field-element payloads (see
#                           edge_client_main.cpp: float32 bundles cannot
#                           carry values up to 2^31-1)


def _read_i64(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        magic = int.from_bytes(f.read(4), "little")
        if magic != _I64_MAGIC:
            raise ValueError(f"{path}: not an FTI8 payload")
        n = int.from_bytes(f.read(8), "little")
        arr = np.fromfile(f, dtype="<i8", count=n)
    if len(arr) != n:
        raise ValueError(f"{path}: truncated ({len(arr)}/{n})")
    return arr


def export_client_data(path: str, x: np.ndarray, y: np.ndarray) -> None:
    """Write one client's local dataset as an edge data bundle (features
    flattened — the native MLP consumes (n, d))."""
    write_bundle(path, {
        "x": np.asarray(x, np.float32).reshape(len(y), -1),
        "y": np.asarray(y, np.float32),
    })


class EdgeFederationServer:
    """Aggregation server for native edge-client processes."""

    def __init__(self, work_dir: str, model: Dict[str, np.ndarray],
                 num_clients: int, rounds: int = 1, epochs: int = 1,
                 batch_size: int = 32, lr: float = 0.05, seed: int = 0,
                 round_timeout_s: float = 120.0,
                 secure: Optional[tuple] = None):
        """``secure=(U, T)`` switches the round to the LightSecAgg protocol
        (N = num_clients): clients upload MASKED quantized weights plus LCC
        mask shares, the server announces the accepted sources
        (``survivors.txt``), collects any U aggregate shares, one-shot
        decodes the SUM mask (``core.mpc.lightsecagg``), and unmasks the
        aggregate — the server never sees an individual update, and up to
        N - U clients may drop after uploading without losing their
        contribution.  C++ twin: ``native/edge_client_main.cpp`` secure
        path (reference MobileNN ``src/security/LightSecAgg.cpp``)."""
        self.work_dir = work_dir
        os.makedirs(work_dir, exist_ok=True)
        self.model = {k: np.asarray(v, np.float32) for k, v in model.items()}
        self.num_clients = int(num_clients)
        self.rounds = int(rounds)
        self.epochs = int(epochs)
        self.batch_size = int(batch_size)
        self.lr = float(lr)
        self.seed = int(seed)
        self.timeout = float(round_timeout_s)
        self.secure = None
        if secure is not None:
            u, t = int(secure[0]), int(secure[1])
            if not (0 < t < u <= self.num_clients):
                raise ValueError(f"need 0 < T < U <= N, got U={u} T={t} "
                                 f"N={self.num_clients}")
            self.secure = (u, t)
        self.history: List[Dict[str, float]] = []

    # -- protocol steps ----------------------------------------------------
    def _publish_round(self, r: int) -> str:
        rdir = os.path.join(self.work_dir, f"round_{r}")
        os.makedirs(rdir, exist_ok=True)
        write_bundle(os.path.join(rdir, "global.fteb"), self.model)
        task = (f"round={r}\nepochs={self.epochs}\nbatch={self.batch_size}\n"
                f"lr={self.lr}\nseed={self.seed}\n")
        if self.secure is not None:
            u, t = self.secure
            task += (f"secure=1\nlsa_n={self.num_clients}\nlsa_u={u}\n"
                     f"lsa_t={t}\n")
        tmp = os.path.join(rdir, "task.txt.tmp")
        with open(tmp, "w") as f:
            f.write(task)
        os.rename(tmp, os.path.join(rdir, "task.txt"))  # atomic publish
        return rdir

    def _collect(self, rdir: str) -> Optional[List[Dict]]:
        deadline = time.time() + self.timeout
        results: Dict[int, Dict] = {}
        while time.time() < deadline and len(results) < self.num_clients:
            for c in range(self.num_clients):
                if c in results:
                    continue
                done = os.path.join(rdir, f"client_{c}.done")
                blob = os.path.join(rdir, f"client_{c}.fteb")
                if not (os.path.exists(done) and os.path.exists(blob)):
                    continue
                results[c] = {"meta": self._read_meta(done),
                              "params": read_bundle(blob)}
            if len(results) < self.num_clients:
                time.sleep(0.02)
        if len(results) < self.num_clients:
            return None
        return [results[c] for c in range(self.num_clients)]

    def _aggregate(self, results: List[Dict]) -> None:
        total = sum(r["meta"].get("n_samples", 1.0) for r in results)
        agg = {k: np.zeros_like(v) for k, v in self.model.items()}
        for r in results:
            w = r["meta"].get("n_samples", 1.0) / max(total, 1.0)
            for k in agg:
                agg[k] += w * np.asarray(r["params"][k], np.float32)
        self.model = agg

    # -- secure (LightSecAgg) round ----------------------------------------
    def _read_meta(self, path: str) -> Dict[str, float]:
        meta: Dict[str, float] = {}
        with open(path) as f:
            for line in f:
                if "=" in line:
                    k, v = line.strip().split("=", 1)
                    meta[k] = float(v)
        return meta

    def _secure_round(self, r: int, rdir: str) -> float:
        """One LightSecAgg round against the native clients.  Returns the
        mean reported client loss.  Aggregation is the UNWEIGHTED mean of
        the surviving sources (sample-count weighting would have to be
        applied client-side, before masking — the server never sees
        plaintext to weight)."""
        from ..core.mpc.lightsecagg import decode_aggregate_mask
        from ..core.mpc.secagg import P, dequantize

        u, t = self.secure
        k = u - t
        # phase 1: masked updates + coded shares from the sources.  Exit
        # early once every client reported, or once >= U sources are in
        # and a grace window has passed — a client that died BEFORE
        # uploading must not stall each round for the full timeout (the
        # protocol only needs U)
        deadline = time.time() + self.timeout
        grace_s = min(2.0, self.timeout / 4)
        quorum_at: Optional[float] = None
        sources: Dict[int, Dict] = {}
        while time.time() < deadline and len(sources) < self.num_clients:
            for c in range(self.num_clients):
                if c in sources:
                    continue
                masked = os.path.join(rdir, f"client_{c}.masked.i64")
                shares = os.path.join(rdir, f"shares_{c}.i64")
                done = os.path.join(rdir, f"client_{c}.done")
                if all(os.path.exists(p) for p in (masked, shares, done)):
                    sources[c] = {"masked": _read_i64(masked),
                                  "meta": self._read_meta(done)}
            if len(sources) >= u:
                if quorum_at is None:
                    quorum_at = time.time()
                elif time.time() - quorum_at > grace_s:
                    break
            if len(sources) < self.num_clients:
                time.sleep(0.02)
        if len(sources) < u:
            raise TimeoutError(
                f"secure round {r}: only {len(sources)} sources reported "
                f"(need U={u}) within {self.timeout}s")
        survivors = sorted(sources)
        tmp = os.path.join(rdir, "survivors.txt.tmp")
        with open(tmp, "w") as f:
            f.write("".join(f"{c}\n" for c in survivors))
        os.rename(tmp, os.path.join(rdir, "survivors.txt"))
        # phase 2: any U aggregate shares reconstruct the sum mask — a
        # source that dropped AFTER uploading still contributes (that is
        # the LightSecAgg one-shot-reconstruction property)
        aggs: Dict[int, np.ndarray] = {}
        deadline = time.time() + self.timeout
        while time.time() < deadline and len(aggs) < u:
            for c in survivors:
                if c + 1 in aggs:
                    continue
                p = os.path.join(rdir, f"client_{c}.aggshare.i64")
                if os.path.exists(p):
                    aggs[c + 1] = _read_i64(p)
            if len(aggs) < u:
                time.sleep(0.02)
        if len(aggs) < u:
            raise TimeoutError(
                f"secure round {r}: only {len(aggs)} aggregate shares "
                f"(need U={u}) within {self.timeout}s")
        d = len(sources[survivors[0]]["masked"])
        block = -(-d // k)
        g = decode_aggregate_mask(aggs, k * block, u)
        sum_mask = g[:k].reshape(-1)[:d]
        total = np.zeros(d, np.int64)
        for c in survivors:
            total = (total + sources[c]["masked"]) % P
        flat = dequantize((total - sum_mask) % P) / len(survivors)
        # unflatten in the C++ client's w1,b1[,w2,b2] order
        off = 0
        new_model = {}
        for name in ("w1", "b1", "w2", "b2"):
            if name not in self.model:
                continue
            n = self.model[name].size
            new_model[name] = flat[off:off + n].reshape(
                self.model[name].shape).astype(np.float32)
            off += n
        if off != d:
            raise ValueError(f"flat vector length {d} != model size {off}")
        self.model = new_model
        return float(np.mean([sources[c]["meta"].get("loss", np.nan)
                              for c in survivors]))

    # -- lifecycle ---------------------------------------------------------
    def run(self) -> Dict[str, np.ndarray]:
        for r in range(self.rounds):
            rdir = self._publish_round(r)
            if self.secure is not None:
                mean_loss = self._secure_round(r, rdir)
            else:
                results = self._collect(rdir)
                if results is None:
                    raise TimeoutError(
                        f"round {r}: not all {self.num_clients} edge "
                        f"clients reported within {self.timeout}s")
                self._aggregate(results)
                mean_loss = float(np.mean(
                    [res["meta"].get("loss", np.nan) for res in results]))
            self.history.append({"round": r, "loss": mean_loss})
            log.info("edge federation round %d: mean client loss %.4f", r,
                     mean_loss)
        self.finish()
        return self.model

    def finish(self) -> None:
        tmp = os.path.join(self.work_dir, "finish.txt.tmp")
        with open(tmp, "w") as f:
            f.write("done\n")
        os.rename(tmp, os.path.join(self.work_dir, "finish.txt"))


def build_client_binary() -> str:
    """Compile the standalone edge client (cached beside the sources).

    The mtime cache alone is not enough: a binary built on another machine
    (different glibc/libstdc++) loads fine there but aborts with
    ``GLIBC_x.y not found`` here, and every client subprocess then dies
    instantly while the server polls to timeout.  So a cached binary must
    also prove it EXECUTES on this host (argc<2 exits with the usage
    message, which is all we need) before it is trusted."""
    import subprocess
    src_dir = os.path.dirname(os.path.abspath(__file__))
    native = os.path.join(os.path.dirname(src_dir), "native")
    out = os.path.join(native, "fedml_edge_client")
    srcs = [os.path.join(native, "edge_client_main.cpp"),
            os.path.join(native, "edge_trainer.cpp")]

    def _loads_here() -> bool:
        try:
            r = subprocess.run([out], capture_output=True, timeout=10)
        except (OSError, subprocess.TimeoutExpired):
            return False
        # usage exit is fine; a loader failure mentions GLIBC/GLIBCXX
        return b"GLIBC" not in r.stderr

    if (not os.path.exists(out)
            or any(os.path.getmtime(s) > os.path.getmtime(out)
                   for s in srcs)
            or not _loads_here()):
        # build beside the target and rename: two processes that find no
        # binary (test workers) must not write one file at once
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(["g++", "-O2", "-std=c++17", *srcs, "-o", tmp],
                       check=True)
        os.replace(tmp, out)
    return out
