"""Cross-device server (reference ``cross_device/mnn_server.py:6``
``ServerMNN``): Python server only; edge clients are native (the reference's
Android/MNN C++ SDK; here the C++ edge trainer in ``fedml_tpu/native``).

Transport: the filestore backend's control/data split (equivalent to the
reference's MQTT+S3-MNN pair).  The model travels as the portable edge
bundle (msgpack'd flat arrays, see ``native/edge_bundle.py``) instead of an
MNN graph file — the C ABI trainer consumes exactly that format.
"""

from __future__ import annotations

import os
import tempfile

from ..cross_silo.server import FedMLAggregator, FedMLServerManager


class ServerMNN:
    """``client_backend`` (args) selects the edge transport:

    - default — Python edge clients over the cross-silo FSM (filestore
      control/data split);
    - ``"native"`` — the C++ edge-client binary as the client PROCESS,
      driven through the shared-directory edge protocol
      (:mod:`.edge_federation`), the reference's MNN-phone regime.
    """

    def __init__(self, args, device, dataset, model, server_aggregator=None):
        self.args = args
        self.dataset = dataset
        self.model = model
        self.native = str(getattr(args, "client_backend", "")) == "native"
        if self.native:
            return  # run() drives the edge federation directly
        client_num = int(getattr(args, "client_num_per_round", 1))
        size = client_num + 1
        backend = str(getattr(args, "backend", "filestore"))
        if backend in ("sp", "mesh", "MPI", "NCCL", "MQTT_S3_MNN"):
            backend = "filestore"
        self.aggregator = FedMLAggregator(args, model, dataset, client_num)
        if server_aggregator is not None:
            self.aggregator.user_aggregator = server_aggregator
        self.server_manager = FedMLServerManager(
            args, self.aggregator, rank=0, size=size, backend=backend)

    def run(self):
        if self.native:
            return self._run_native()
        self.server_manager.run()
        return self.aggregator.get_global_model_params()

    # -- native edge-client regime ----------------------------------------
    def _run_native(self):
        """Full federated run with C++ edge-client subprocesses (reference
        cross_device: Python server + MNN phones; here server + native
        binaries over the shared-dir protocol).  Returns final flax
        params."""
        import subprocess

        import jax

        from ..native.edge_bundle import (edge_model_to_flax,
                                          flax_to_edge_model)
        from .edge_federation import (EdgeFederationServer,
                                      build_client_binary,
                                      export_client_data)

        args = self.args
        n_clients = int(getattr(args, "client_num_per_round", 2))
        work_dir = str(getattr(args, "edge_work_dir", "") or
                       tempfile.mkdtemp(prefix="fedml_edge_fed_"))
        params0 = self.model.init(jax.random.PRNGKey(
            int(getattr(args, "random_seed", 0))))
        edge_model = flax_to_edge_model(params0)

        data_dir = os.path.join(work_dir, "client_data")
        os.makedirs(data_dir, exist_ok=True)
        procs = []
        binary = build_client_binary()
        spawn = bool(getattr(args, "edge_spawn_clients", True))
        for c in range(n_clients):
            idx = self.dataset.client_idxs[c % self.dataset.num_clients]
            path = os.path.join(data_dir, f"client_{c}.fteb")
            export_client_data(path, self.dataset.train_x[idx],
                               self.dataset.train_y[idx])
            if spawn:
                # native C++ clients: they never load jax, so this server
                # process may hold the chip while they run
                procs.append(subprocess.Popen(
                    [binary, work_dir, str(c), path, "20"],
                    stderr=subprocess.DEVNULL))
        srv = EdgeFederationServer(
            work_dir, edge_model, num_clients=n_clients,
            rounds=int(getattr(args, "comm_round", 1)),
            epochs=int(getattr(args, "epochs", 1)),
            batch_size=int(getattr(args, "batch_size", 32)),
            lr=float(getattr(args, "learning_rate", 0.05)),
            seed=int(getattr(args, "random_seed", 0)),
            round_timeout_s=float(getattr(args, "aggregation_timeout_s", 0)
                                  or 120.0))
        try:
            final_edge = srv.run()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
        self.history = srv.history
        return edge_model_to_flax(final_edge, params0)
