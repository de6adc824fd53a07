"""fedml_tpu — a TPU-native federated learning + MLOps framework.

Capability parity with the reference FedML (``/root/reference``), rebuilt
idiomatically for TPU: clients are a mesh axis, local SGD is a scanned jitted
step, aggregation is ``psum`` over ICI, and the message-passing layer is a
thin WAN shim instead of the core (see SURVEY.md §7 design stance).

Public surface parity (reference ``python/fedml/__init__.py``):
``init / run_simulation / run_cross_silo_server / run_cross_silo_client /
run_hierarchical_cross_silo_* / run_mnn_server``, plus the ``device``,
``data``, ``model``, ``mlops`` modules.
"""

from __future__ import annotations

import logging
import os
import random
from typing import Optional

import numpy as np

__version__ = "0.1.0"

# Persistent XLA compilation cache.  Its place comes from outside: where
# JAX_COMPILATION_CACHE_DIR is set jax reads it itself and nothing here
# touches the setting; otherwise the cache is ONE fixed directory inside the
# checkout.  A directory whose name changes from run to run (a pid, a time,
# a temporary directory) would never be hit again.  A process that was told
# to use the CPU (JAX_PLATFORMS=cpu: the tests, the census tools) gets none:
# XLA:CPU programs compile in seconds, and their cached form reloads with a
# machine-feature warning even on the machine that wrote it.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        and os.environ.get("JAX_PLATFORMS") != "cpu":
    import jax as _jax

    _jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)

from . import constants  # noqa: E402
from .arguments import Arguments, add_args, load_arguments  # noqa: E402
from .constants import (  # noqa: E402
    FEDML_SIMULATION_TYPE_MESH,
    FEDML_SIMULATION_TYPE_SP,
    FEDML_TRAINING_PLATFORM_CROSS_DEVICE,
    FEDML_TRAINING_PLATFORM_CROSS_SILO,
    FEDML_TRAINING_PLATFORM_SIMULATION,
)

_global_training_type: Optional[str] = None
_global_comm_backend: Optional[str] = None


def init(args: Optional[Arguments] = None, check_env: bool = True,
         should_init_logs: bool = True) -> Arguments:
    """Parity with ``fedml.init`` (reference ``python/fedml/__init__.py:64``):
    load args (YAML + CLI), seed host RNGs, init mlops, dispatch per-mode
    setup.  Device RNG is handled by explicit threefry keys (core/rng.py), so
    host seeding matters only for numpy-side sampling."""
    if args is None:
        args = load_arguments(_global_training_type, _global_comm_backend)
    from .arguments import validate_args
    validate_args(args)
    seed = int(getattr(args, "random_seed", 0))
    random.seed(seed)
    np.random.seed(seed)
    if should_init_logs:
        logging.basicConfig(
            level=logging.INFO,
            format="[fedml_tpu] %(asctime)s %(levelname)s %(name)s: %(message)s")
    from . import mlops
    mlops.init(args)

    t = str(getattr(args, "training_type", FEDML_TRAINING_PLATFORM_SIMULATION))
    if t == FEDML_TRAINING_PLATFORM_CROSS_SILO:
        _update_client_id_list(args)
    return args


def _update_client_id_list(args):
    """Reference ``__init__.py:409``: normalize client_id_list for cross-silo
    runs so the server knows its expected client set."""
    n = int(getattr(args, "client_num_in_total", 0) or 0)
    cur = getattr(args, "client_id_list", None)
    if not cur or cur in ("[]", "None"):
        args.client_id_list = list(range(1, n + 1))
    elif isinstance(cur, str):
        import json
        try:
            args.client_id_list = json.loads(cur)
        except json.JSONDecodeError:
            args.client_id_list = list(range(1, n + 1))


# -- one-line launchers (reference launch_simulation.py / launch_cross_silo*)
def run_simulation(backend: str = FEDML_SIMULATION_TYPE_SP, args=None,
                   client_trainer=None, server_aggregator=None):
    """Parity with ``fedml.run_simulation`` (reference
    ``python/fedml/launch_simulation.py:9``)."""
    global _global_training_type, _global_comm_backend
    _global_training_type = FEDML_TRAINING_PLATFORM_SIMULATION
    _global_comm_backend = backend
    if args is None:
        args = init()
    args.training_type = FEDML_TRAINING_PLATFORM_SIMULATION
    args.backend = backend
    from . import data as data_mod
    from . import device as device_mod
    from . import model as model_mod
    from .runner import FedMLRunner

    dev = device_mod.get_device(args)
    dataset, output_dim = data_mod.load(args)
    model = model_mod.create(args, output_dim)
    runner = FedMLRunner(args, dev, dataset, model, client_trainer,
                         server_aggregator)
    return runner.run()


def _run_cross_silo(role: str, args=None, client_trainer=None,
                    server_aggregator=None, scenario: str = "horizontal"):
    global _global_training_type
    _global_training_type = FEDML_TRAINING_PLATFORM_CROSS_SILO
    if args is None:
        args = init()
    args.training_type = FEDML_TRAINING_PLATFORM_CROSS_SILO
    args.role = role
    args.scenario = getattr(args, "scenario", scenario) or scenario
    from . import data as data_mod
    from . import device as device_mod
    from . import model as model_mod
    from .runner import FedMLRunner

    dev = device_mod.get_device(args)
    dataset, output_dim = data_mod.load(args)
    model = model_mod.create(args, output_dim)
    return FedMLRunner(args, dev, dataset, model, client_trainer,
                       server_aggregator).run()


def run_cross_silo_server(args=None, server_aggregator=None):
    return _run_cross_silo("server", args, None, server_aggregator)


def run_cross_silo_client(args=None, client_trainer=None):
    return _run_cross_silo("client", args, client_trainer, None)


def run_hierarchical_cross_silo_server(args=None, server_aggregator=None):
    return _run_cross_silo("server", args, None, server_aggregator,
                           scenario="hierarchical")


def run_hierarchical_cross_silo_client(args=None, client_trainer=None):
    return _run_cross_silo("client", args, client_trainer, None,
                           scenario="hierarchical")


def run_mnn_server(args=None, server_aggregator=None):
    """Cross-device server (reference ``fedml.run_mnn_server``)."""
    global _global_training_type
    _global_training_type = FEDML_TRAINING_PLATFORM_CROSS_DEVICE
    if args is None:
        args = init()
    args.training_type = FEDML_TRAINING_PLATFORM_CROSS_DEVICE
    from . import data as data_mod
    from . import device as device_mod
    from . import model as model_mod
    from .runner import FedMLRunner

    dev = device_mod.get_device(args)
    dataset, output_dim = data_mod.load(args)
    model = model_mod.create(args, output_dim)
    return FedMLRunner(args, dev, dataset, model, None, server_aggregator).run()


def run_model_serving_server(args, end_point_name, model_name,
                             model_version="", dataset=None, model=None,
                             server_aggregator=None):
    """Federated serving server (reference ``fedml.run_model_serving_server``,
    ``__init__.py:520-546`` exports)."""
    from .serving import FedMLModelServingServer
    return FedMLModelServingServer(
        args, end_point_name, model_name, model_version, dataset=dataset,
        model=model, server_aggregator=server_aggregator).run()


def run_model_serving_client(args, end_point_name, model_name,
                             model_version="", dataset=None, model=None,
                             client_trainer=None):
    """Federated serving client (reference ``fedml.run_model_serving_client``)."""
    from .serving import FedMLModelServingClient
    return FedMLModelServingClient(
        args, end_point_name, model_name, model_version, dataset=dataset,
        model=model, client_trainer=client_trainer).run()


# module namespaces mirroring `fedml.data` / `fedml.model` / `fedml.device`
from . import data  # noqa: E402
from . import device  # noqa: E402
from . import mlops  # noqa: E402
from . import model  # noqa: E402

# user metric APIs re-exported at top level (reference __init__.py:547-566)
from .mlops import (log, log_artifact, log_endpoint, log_llm_record,  # noqa: E402
                    log_metric, log_model)

__all__ = [
    "init", "run_simulation", "run_cross_silo_server", "run_cross_silo_client",
    "run_hierarchical_cross_silo_server", "run_hierarchical_cross_silo_client",
    "run_mnn_server", "run_model_serving_server", "run_model_serving_client",
    "Arguments", "add_args", "load_arguments",
    "log", "log_metric", "log_artifact", "log_model", "log_llm_record",
    "log_endpoint",
    "constants", "data", "device", "model", "mlops", "__version__",
]
