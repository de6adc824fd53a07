"""Two-boundary hierarchical federation (SURVEY §7's last untested
architectural claim; VERDICT r2 item 9): cross-silo traffic rides REAL
gRPC sockets between OS processes, while each client process trains on a
REAL multi-device silo mesh (4 virtual CPU devices) with the batch sharded
over the silo's data axis — the TPU-native analog of the reference's
torchrun-intra-silo + gRPC-cross-silo hierarchical scenario
(``cross_silo/client/fedml_client_master_manager.py:200``)."""

import socket
import textwrap

import pytest


@pytest.mark.slow
def test_hierarchical_mesh_intra_silo_grpc_cross_silo(tmp_path):
    from fedml_tpu.cross_silo.client.client_launcher import CrossSiloLauncher

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        base_port = s.getsockname()[1]

    entry = tmp_path / "entry.py"
    out_acc = tmp_path / "final_acc.txt"
    entry.write_text(textwrap.dedent(f"""
        import os
        from fedml_tpu.cross_silo.client.client_launcher import (
            env_rank, env_role, env_run_id)
        role = env_role()
        if role == "client":
            # each client process IS a silo: 4 virtual local devices make
            # the intra-silo data-parallel mesh
            os.environ["XLA_FLAGS"] = \\
                "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        if role == "client":
            jax.config.update("jax_num_cpu_devices", 4)

        import fedml_tpu
        from fedml_tpu import data as data_mod, model as model_mod

        args = fedml_tpu.load_arguments()
        args.update(
            training_type="cross_silo", backend="GRPC",
            grpc_base_port={base_port}, rank=env_rank(), role=role,
            run_id=env_run_id(), scenario="hierarchical",
            n_proc_in_silo=4, dataset="synthetic", num_classes=4,
            input_shape=(8, 8, 1), train_size=256, test_size=64,
            model="lr", client_num_in_total=2, client_num_per_round=2,
            comm_round=2, epochs=1, batch_size=16, learning_rate=0.1,
            random_seed=3, client_id_list=[1, 2],
            frequency_of_the_test=1,
        )
        args = fedml_tpu.init(args, should_init_logs=False)
        dataset, out_dim = data_mod.load(args)
        model = model_mod.create(args, out_dim)
        if role == "server":
            from fedml_tpu.cross_silo.server import Server
            srv = Server(args, None, dataset, model)
            srv.run()
            acc = srv.aggregator.test_on_server_for_all_clients(1)
            with open({str(out_acc)!r}, "w") as f:
                f.write(str(acc))
        else:
            from fedml_tpu.cross_silo.client import Client
            client = Client(args, None, dataset, model)
            pg = client.client_manager.trainer_adapter.process_group_manager
            assert pg is not None, "hierarchical scenario built no silo mesh"
            with open({str(tmp_path)!r} +
                      f"/silo_mesh_{{env_rank()}}.txt", "w") as f:
                f.write(str(pg.world_size))
            client.run()
    """))

    launcher = CrossSiloLauncher(str(entry), run_id="dcn1",
                                 client_ranks=[1, 2])
    codes = launcher.run(timeout_s=420)
    assert codes == [0, 0, 0]
    acc = float(out_acc.read_text())
    assert acc > 0.4, acc
    for rank in (1, 2):
        ws = int((tmp_path / f"silo_mesh_{rank}.txt").read_text())
        assert ws == 4, f"client {rank} silo mesh was {ws}-way, wanted 4"
