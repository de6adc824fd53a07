"""Federated LLM fine-tuning — the rebuild of reference ``train/llm/``
(HF Trainer + DeepSpeed ZeRO + PEFT/LoRA, ``hf_trainer.py:28`` /
``peft_utils.py``), redesigned for the BASELINE north star: 512-client
Llama LoRA federation at ≥1 round/min on a pod.

Memory layout (SURVEY §7 hard parts): ONE copy of the base weights —
replicated or model-axis sharded — while per-client state is ONLY the LoRA
adapters (collection "lora", ~0.1% of params).  The cohort's local training
vmaps over stacked adapters against the shared base; the federated merge
averages adapters only.  Gradients flow exclusively to adapters, so the
backward pass never materializes base-weight gradients.
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core import rng as rng_util
from ..core import tree as tree_util
from ..data.federated_dataset import FederatedDataset
from ..obs import get_tracer
from ..obs import programs as obs_programs
from ..obs.jaxhooks import count_put
from .model import (LlamaLM, causal_nll, config_from_args,
                    per_sequence_loglik)

log = logging.getLogger(__name__)


def lora_init(key, lora_zeros):
    """Randomize every 'A' leaf (normal·0.02), keep 'B' zero — adapters start
    as identity (reference PEFT default)."""
    flat = jax.tree_util.tree_flatten_with_path(lora_zeros)[0]
    treedef = jax.tree_util.tree_structure(lora_zeros)
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        names = [getattr(p, "key", "") for p in path]
        if "A" in names:
            leaves.append(0.02 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, leaf.dtype))
        else:
            leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def rank_mask_tree(lora_template, mask_vec):
    """Per-leaf masks that zero every rank component ≥ a client's rank:
    'A' leaves (in, R) mask the last axis, 'B' leaves (R, out) the first.
    ``mask_vec`` is the (R,) 0/1 vector for one client."""
    flat = jax.tree_util.tree_flatten_with_path(lora_template)[0]
    treedef = jax.tree_util.tree_structure(lora_template)
    masks = []
    for path, leaf in flat:
        names = [getattr(p, "key", "") for p in path]
        if "A" in names:
            masks.append(mask_vec[None, :].astype(leaf.dtype))
        elif "B" in names:
            masks.append(mask_vec[:, None].astype(leaf.dtype))
        else:
            masks.append(jnp.ones((1,) * leaf.ndim, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, masks)


class FedLLMAPI:
    """FedAvg over LoRA adapters of a causal LM."""

    def __init__(self, args, dataset: FederatedDataset, mesh=None):
        self.args = args
        self.dataset = dataset
        self.seed = int(getattr(args, "random_seed", 0))
        self.batch_size = int(getattr(args, "batch_size", 2))
        self.epochs = int(getattr(args, "epochs", 1))
        self.comm_rounds = int(getattr(args, "comm_round", 5))
        self.clients_per_round = int(getattr(args, "client_num_per_round", 4))
        self.max_steps = int(getattr(args, "llm_max_local_steps", 4))
        lr = float(getattr(args, "learning_rate", 1e-3))

        cfg = config_from_args(args, dataset.num_classes)
        if cfg.lora_rank == 0:
            import dataclasses
            cfg = dataclasses.replace(
                cfg, lora_rank=int(getattr(args, "lora_rank", 8)),
                lora_alpha=float(getattr(args, "lora_alpha", 16.0)))
        self.cfg = cfg
        self.model = LlamaLM(cfg)
        self.tx = optax.adamw(lr, weight_decay=0.0)

        # heterogeneous adapter capacity (HetLoRA-style): device classes
        # train different ranks of the same global adapters
        ranks = getattr(args, "lora_rank_per_client", None)
        self.client_ranks = None
        if ranks is not None:
            ranks = np.asarray(ranks, np.int32)
            if len(ranks) != dataset.num_clients:
                raise ValueError(
                    f"lora_rank_per_client has {len(ranks)} entries for "
                    f"{dataset.num_clients} clients")
            if ranks.min() < 1 or ranks.max() > cfg.lora_rank:
                raise ValueError(
                    f"per-client ranks must be in [1, {cfg.lora_rank}], "
                    f"got [{ranks.min()}, {ranks.max()}]")
            self.client_ranks = ranks

        key = rng_util.root_key(self.seed)
        seq = dataset.train_x.shape[1]
        dummy = jnp.zeros((1, seq), jnp.int32)
        # The base is FROZEN under LoRA, so init emits matmul weights and
        # embeddings directly in cfg.store_dtype (bf16 by default — halves
        # weight HBM vs f32 masters; see LlamaConfig.param_dtype). RMSNorm
        # scales and MoE router kernels stay f32 (precision-sensitive).
        self.mesh = mesh
        self._client_sharding = None
        if mesh is not None:
            # GSPMD mesh regime (the 512-client pod path): base params laid
            # out by the TP/FSDP rules over ``model``, adapters + optimizer
            # state replicated, the cohort axis of every round tensor sharded
            # over ``client`` — XLA turns the weighted adapter merge into one
            # psum over ICI.  Weights materialize DIRECTLY into the sharded
            # layout (jit with out_shardings over an eval_shape skeleton):
            # an init-then-device_put would momentarily hold a full
            # unsharded copy — measured at exactly 1x base weights of extra
            # footprint on the virtual mesh (round-5 --dump-live audit),
            # and a guaranteed host-OOM for 7B-class configs on real pods.
            from jax.sharding import NamedSharding
            from ..core.mesh import client_sharded, replicated
            from .model import param_sharding_rules

            abstract = jax.eval_shape(self.model.init,
                                      rng_util.purpose_key(key, "init"),
                                      dummy)
            rules = param_sharding_rules(abstract["params"], mesh)
            out_sh = {
                "params": jax.tree_util.tree_map(
                    lambda spec: NamedSharding(mesh, spec), rules),
                "lora": jax.tree_util.tree_map(
                    lambda _: replicated(mesh), abstract["lora"]),
            }
            variables = jax.jit(self.model.init,
                                out_shardings=out_sh)(
                rng_util.purpose_key(key, "init"), dummy)
            self._client_sharding = client_sharded(mesh)
        else:
            variables = self.model.init(rng_util.purpose_key(key, "init"),
                                        dummy)
        self.base_params = variables["params"]
        self.global_lora = lora_init(rng_util.purpose_key(key, "lora"),
                                     variables["lora"])
        if mesh is not None:
            self.global_lora = jax.device_put(self.global_lora,
                                              replicated(mesh))
        # on a mesh, pin the merged adapters (and the loss) back to the
        # replicated resting placement: left to GSPMD they come out sharded
        # over ``model``, and round 1 then compiles a second program for the
        # new input layout
        self._round_fn = jax.jit(
            self._build_round_fn(),
            out_shardings=(None if mesh is None
                           else (replicated(mesh), replicated(mesh))))
        # the round program's registration with obs/programs.py, made at
        # its first launch (``program_ops``); None until then
        self._round_program = None

    # -- pure round --------------------------------------------------------
    def _build_round_fn(self):
        model, tx = self.model, self.tx
        alpha_steps = self.max_steps

        chunk = int(getattr(self.cfg, "streaming_xent_chunk", 0) or 0)
        # chunk > vocab would PAD the head matmul up to the chunk width
        # (32x the work for a 256-vocab model at the tooling default 8192)
        chunk = min(chunk, self.cfg.vocab_size)
        if chunk:
            from fedml_tpu.ops.xent import streaming_xent

            def loss_fn(lora, base, x, y):
                h = model.apply({"params": base, "lora": lora}, x,
                                return_hidden=True)
                return streaming_xent(h, base["lm_head"]["kernel"], y, chunk)
        else:
            def loss_fn(lora, base, x, y):
                logits = model.apply({"params": base, "lora": lora}, x)
                return causal_nll(logits, y)

        def local_train(lora0, base, xb, yb, mask, rank_vec):
            # heterogeneous ranks (HetLoRA-style): a rank-r client receives
            # and trains only the first r rank components; the rest stay
            # exactly zero through init AND gradient masking
            mtree = rank_mask_tree(lora0, rank_vec)
            lora0 = jax.tree_util.tree_map(jnp.multiply, lora0, mtree)
            opt0 = tx.init(lora0)

            def step(carry, inp):
                lora, opt = carry
                (x, y), m = inp
                loss, grads = jax.value_and_grad(loss_fn)(lora, base, x, y)
                grads = tree_util.tree_scale(grads, m)
                grads = jax.tree_util.tree_map(jnp.multiply, grads, mtree)
                updates, opt_new = tx.update(grads, opt, lora)
                lora_new = optax.apply_updates(lora, updates)
                keep = m > 0
                sel = lambda n, o: jnp.where(keep, n, o)
                lora_new = jax.tree_util.tree_map(sel, lora_new, lora)
                opt_new = jax.tree_util.tree_map(sel, opt_new, opt)
                return (lora_new, opt_new), loss * m

            (lora, _), losses = jax.lax.scan(step, (lora0, opt0),
                                             ((xb, yb), mask))
            n = jnp.maximum(jnp.sum(mask), 1.0)
            return lora, jnp.sum(losses) / n

        def round_fn(base, global_lora, x, y, mask, weights, rank_masks):
            # every client starts from the global adapters; base broadcast
            loras0 = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(l, (x.shape[0],) + l.shape),
                global_lora)
            loras, losses = jax.vmap(
                lambda l0, xb, yb, mb, rv: local_train(l0, base, xb, yb,
                                                       mb, rv)
            )(loras0, x, y, mask, rank_masks)
            # component-wise merge: each rank component averages only over
            # the clients that HOLD it (homogeneous masks reduce exactly to
            # the plain weighted average)
            stacked_masks = jax.vmap(
                lambda rv: rank_mask_tree(global_lora, rv))(rank_masks)

            def merge_leaf(stacked, m, g):
                wm = weights.reshape((-1,) + (1,) * (stacked.ndim - 1)) \
                    * jnp.broadcast_to(m, stacked.shape)
                tot = jnp.sum(wm, axis=0)
                avg = jnp.sum(stacked * wm, axis=0) / jnp.maximum(tot, 1e-12)
                # a component held by NOBODY in this cohort keeps its global
                # value — zeroing it would be irreversible (zero A column +
                # zero B row is a dead saddle: gradients identically zero)
                return jnp.where(tot > 0, avg, g)

            merged = jax.tree_util.tree_map(merge_leaf, loras, stacked_masks,
                                            global_lora)
            round_loss = jnp.sum(losses * weights) / jnp.sum(weights)
            return merged, round_loss

        return round_fn

    def _cohort_rank_masks(self, clients) -> np.ndarray:
        """(C, R) 0/1 masks: which rank components each sampled client
        holds (all ones when ranks are homogeneous)."""
        R = self.cfg.lora_rank
        if self.client_ranks is None:
            return np.ones((len(clients), R), np.float32)
        ranks = self.client_ranks[np.asarray(clients)]
        return (np.arange(R)[None, :] < ranks[:, None]).astype(np.float32)

    def train_one_round(self, round_idx: int):
        tracer = get_tracer()
        with tracer.span("fedllm.round", cat="round",
                         round=int(round_idx)) as rnd:
            with tracer.span("fedllm.round.sample", cat="round"):
                clients = rng_util.sample_clients(
                    self.seed, round_idx, self.dataset.num_clients,
                    self.clients_per_round)
                rank_masks = self._cohort_rank_masks(clients)
            with tracer.span("fedllm.round.batches", cat="round"):
                x, y, mask, w = self.dataset.cohort_batches(
                    clients, self.batch_size, self.seed, round_idx,
                    self.epochs, max_steps=self.max_steps)
            # clients x steps x batch x sequence, before any padding
            rnd.set(clients=len(clients), tokens=int(np.prod(x.shape)))
            with tracer.span("fedllm.round.stage", cat="round") as stage:
                staged = self._stage(clients, (x, y, mask, w, rank_masks))
                stage.set(bytes=count_put(tracer, staged))
            with tracer.span("fedllm.round.dispatch", cat="round"):
                if self._round_program is None:
                    self._round_program = obs_programs.register(
                        "round_fn", self._round_fn,
                        (self.base_params, self.global_lora, *staged))
                self.global_lora, loss = self._round_fn(
                    self.base_params, self.global_lora, *staged)
            with tracer.span("fedllm.round.readback", cat="round"):
                loss = float(loss)
        return {"train_loss": loss}

    def program_ops(self):
        """``{"round_fn": {instruction: {"path", "phase", "kernel", "op"}}}``
        of the compiled round program (None before the first round): what
        joins a device trace's operations to the model's modules and to
        forward, recompute and backward (docs/OBSERVABILITY.md, "Device time
        by module").  Lowers and compiles the round again (a load where a
        persistent compile cache is set): not inside a timed round."""
        return obs_programs.program_ops({"round_fn": self._round_program})

    def _stage(self, clients, arrays):
        """The round's host arrays on the device: padded to tile the
        client axis and put sharded over it where there is a mesh."""
        if self._client_sharding is None:
            return tuple(jnp.asarray(a) for a in arrays)
        # host-pad then ONE sharded transfer — never stage the whole
        # cohort on a single chip (the pattern mesh_simulator uses)
        from ..core.mesh import CLIENT_AXIS, pad_to_multiple
        n_shards = self.mesh.shape[CLIENT_AXIS]
        pad_c = pad_to_multiple(len(clients), n_shards) - len(clients)
        if pad_c:  # cohort must tile evenly over the client axis
            arrays = tuple(np.pad(a, [(0, pad_c)] + [(0, 0)] * (a.ndim - 1))
                           for a in arrays)
        return tuple(jax.device_put(jnp.asarray(a), self._client_sharding)
                     for a in arrays)

    def evaluate(self):
        xb, yb, mb = self.dataset.test_batches(batch_size=self.batch_size)

        @jax.jit
        def eval_fn(base, lora, xb, yb, mb):
            def body(carry, inp):
                x, y, m = inp
                logits = self.model.apply({"params": base, "lora": lora}, x)
                mseq = per_sequence_loglik(logits, y)
                return (carry[0] - jnp.sum(mseq * m), carry[1] + jnp.sum(m)), None
            (nll, n), _ = jax.lax.scan(body, (0.0, 0.0), (xb, yb, mb))
            return nll / n

        nll = float(eval_fn(self.base_params, self.global_lora,
                            jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb)))
        return nll

    def _per_client_eval_fn(self):
        """Compiled all-clients NLL program, built once per API instance
        (a per-call ``@jax.jit`` closure would re-trace every call — the
        jit cache is keyed on the function object)."""
        if getattr(self, "_pc_eval", None) is not None:
            return self._pc_eval

        @jax.jit
        def run(base, lora, X, Y, M):
            def per_client(_, inp):
                xb, yb, mb = inp

                def body(carry, b):
                    x, y, m = b
                    logits = self.model.apply(
                        {"params": base, "lora": lora}, x)
                    ll = per_sequence_loglik(logits, y)
                    return (carry[0] - jnp.sum(ll * m),
                            carry[1] + jnp.sum(m)), None

                (nll, n), _ = jax.lax.scan(body, (0.0, 0.0), (xb, yb, mb))
                return None, nll / jnp.maximum(n, 1.0)

            _, nlls = jax.lax.scan(per_client, None, (X, Y, M))
            return nlls

        self._pc_eval = run
        return run

    def evaluate_per_client(self, batch_size: Optional[int] = None):
        """Global adapters scored on every client's LOCAL sequences (the
        LLM flavor of ``FedAvgAPI.evaluate_per_client`` /
        ``_local_test_on_all_clients``): per-client mean NLL plus the
        fairness aggregates — the signal heterogeneous-rank federations
        need to show no device class is left behind."""
        bs = int(batch_size or self.batch_size)
        clients, X, Y, M = self.dataset.pack_per_client(bs)
        run = self._per_client_eval_fn()
        nlls = np.asarray(run(self.base_params, self.global_lora,
                              jnp.asarray(X), jnp.asarray(Y),
                              jnp.asarray(M)))
        return {
            "clients": clients,
            "per_client_nll": nlls,
            "nll_mean": float(nlls.mean()),
            "nll_std": float(nlls.std()),
            "nll_max": float(nlls.max()),       # worst-served client
            "nll_p90": float(np.percentile(nlls, 90)),
        }

    def train(self):
        for r in range(self.comm_rounds):
            t0 = time.time()
            m = self.train_one_round(r)
            log.info("fedllm round %d: loss=%.4f (%.2fs)", r, m["train_loss"],
                     time.time() - t0)
        return self.global_lora
