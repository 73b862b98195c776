"""Training loop driver — the train_from_dataset path.

≙ BoxPSTrainer::Run → BoxPSWorker::TrainFiles (boxps_trainer.cc:282,
boxps_worker.cc:1278): per-batch pack → pull_sparse → ops → push grads →
dense sync → AUC.  TPU-first structure: the whole per-batch pipeline is ONE
jitted, donated function (pull gather + fused seqpool/cvm + MLP fwd/bwd +
scatter-push + sparse optimizer + dense optimizer + AUC bucket update), so
XLA fuses it and the working set never leaves HBM.  Host threads only pack
and prefetch batches (≙ PackBatchTask boxps_worker.cc:1259) through a
bounded Channel.

Dense sync: under a dp-sharded mesh the batch mean IS the global mean, so the
dense gradient allreduce (≙ BoxWrapper::SyncDense NCCL allreduce,
boxps_worker.cc:1191) is implicit in GSPMD — no hand-written collective.
"""

from __future__ import annotations

import threading
import time
from functools import lru_cache, partial
from typing import Dict, Iterator, Optional

import numpy as np
import jax
import jax.numpy as jnp
import optax

from paddlebox_tpu.config import DataFeedConfig, TrainerConfig
from paddlebox_tpu.data.batch_pack import BatchPacker, PackedBatch
from paddlebox_tpu.data.dataset import SlotDataset
from paddlebox_tpu.data.pass_feed import (PackedPassFeed, PlaneStore,
                                          plan_tuple, slice_batch)
from paddlebox_tpu.metrics.auc import (AucCalculator, WuAucCalculator,
                                       accumulate_auc, make_auc_state)
from paddlebox_tpu.ops.seqpool_cvm import fused_seqpool_cvm
from paddlebox_tpu.parallel.topology import HybridTopology
from paddlebox_tpu.ps import embedding, optimizer as sparse_opt
from paddlebox_tpu.ps.pass_manager import BoxPSEngine
from paddlebox_tpu.utils import compile_cache, intervals, trace
from paddlebox_tpu.utils.channel import Channel, ChannelClosed
from paddlebox_tpu.utils.monitor import (stat_add, stat_observe, stat_set,
                                         stat_snapshot)
from paddlebox_tpu.utils.timer import TimerRegistry
from paddlebox_tpu import flags


class SparseTrainer:
    def __init__(self, engine: BoxPSEngine, model, feed_config: DataFeedConfig,
                 batch_size: int, label_slot: str = "label",
                 dense_optimizer=None, use_cvm: bool = True,
                 topology: Optional[HybridTopology] = None,
                 auc_table_size: int = 100_000,
                 trainer_config: Optional[TrainerConfig] = None,
                 amp: bool = False, sparse_path: str = "auto",
                 seed: int = 0):
        self.engine = engine
        self.model = model
        self.packer = BatchPacker(feed_config, batch_size, label_slot)
        # the host planes of packed passes, handed back by _train_packed
        self._plane_store = PlaneStore()
        self.batch_size = batch_size
        self.use_cvm = use_cvm
        self.topology = topology
        self.trainer_config = trainer_config or TrainerConfig()
        self.amp = amp  # bf16 MXU compute for the dense net (master f32)
        # "auto" (_resolve_path picks from what the trainer can see), or a
        # lowering by name: "mxu", "mxu_sharded", "fast", "reference"
        self.sparse_path = sparse_path
        self.timers = TimerRegistry()
        self.slot_ids = np.array(
            [s.slot_id for s in feed_config.sparse_slots], np.int32)

        # dynamic per-slot mf dims (≙ CtrDymfAccessor): mask [S, 3+D] that
        # zeroes each slot's unused tail columns in the pooled features —
        # gradients through the mask zero themselves, so push/optimizer see
        # exact-zero tail grads with no extra work in the hot loop
        self._dym_mask = None
        if engine.config.sgd.slot_mf_dims:
            d_max = engine.config.embedding_dim
            m = np.ones((len(self.slot_ids), 3 + d_max), np.float32)
            for i, sid in enumerate(self.slot_ids):
                m[i, 3 + engine.config.slot_mf_dim(int(sid)):] = 0.0
            self._dym_mask = jnp.asarray(m)

        # models declaring extra feed inputs (e.g. RankAttentionCTR's
        # rank_offset) must have the feed actually produce them — fail at
        # construction, not with an in-trace TypeError mid-pass
        need = set(getattr(model, "extra_inputs", ()))
        have = ({"rank_offset", "ads_offset"}
                | {s.name for s in feed_config.string_slots})
        # a model that owns its loss over unpooled rows (models/looplm.py)
        # says so on its class; the slot it names in seq_key_slot fills the
        # seq_keys plane with its raw keys
        self._row_model = bool(getattr(model, "row_inputs", False))
        self._seq_key_slot = None
        if getattr(model, "seq_key_slot", None) is not None:
            self._seq_key_slot = feed_config.sparse_slots[
                model.seq_key_slot].name
            have.add("seq_keys")
        # a row model whose head is the table's rows names the head's keys
        # (models/sambay.py): the engine keeps them in every pass's working
        # set, the feed carries their rows (head_rows), the step pulls them
        # once and pushes their gradient (mxu_path.pull_head / push head=)
        self._head_keys = None
        if getattr(model, "head_keys", None) is not None:
            if not self._row_model:
                raise ValueError(
                    "model.head_keys: only a model that owns its loss "
                    "(row_inputs) can tie its head to the table's rows")
            self._head_keys = np.asarray(model.head_keys(), np.uint64)
            engine.keep_keys(self._head_keys)
        unknown = need - have
        if unknown:
            raise ValueError(
                f"model.extra_inputs {sorted(unknown)} are not feed planes "
                f"this feed supplies (available: {sorted(have)})")
        if "ads_offset" in need and not feed_config.ads_offset:
            raise ValueError(
                "model requires the ads_offset plane — set "
                "DataFeedConfig(ads_offset=True) (and call "
                "dataset.preprocess_instance())")
        if "rank_offset" in need:
            if not feed_config.rank_offset:
                raise ValueError(
                    "model requires the rank_offset plane — set "
                    "DataFeedConfig(rank_offset=True) (and call "
                    "dataset.preprocess_instance() so batches hold whole "
                    "page views)")
            mr = getattr(model, "max_rank", None)
            if mr is not None and mr != feed_config.max_rank:
                raise ValueError(
                    f"model.max_rank={mr} != DataFeedConfig.max_rank="
                    f"{feed_config.max_rank}: rank_param blocks would be "
                    "mis-addressed")

        self.dense_tx = dense_optimizer or optax.adam(1e-3)
        self.params = model.init(jax.random.PRNGKey(seed))
        self.opt_state = self.dense_tx.init(self.params)
        # ≙ BoxPSAsynDenseTable (dense_sync_mode="async_table"): dense
        # params live in a CPU table updated by a background thread; the
        # jitted step only *computes* dense grads
        self.async_dense = None
        if self.trainer_config.dense_sync_mode == "async_table":
            if dense_optimizer is not None:
                raise ValueError(
                    "dense_sync_mode='async_table' uses the table's own "
                    "adam rule (TrainerConfig.async_dense_*); an explicit "
                    "dense_optimizer would be silently ignored")
            from paddlebox_tpu.trainer.async_dense import AsyncDenseTable
            tc = self.trainer_config
            self.async_dense = AsyncDenseTable(
                self.params, learning_rate=tc.async_dense_learning_rate,
                beta1=tc.async_dense_beta1, beta2=tc.async_dense_beta2,
                eps=tc.async_dense_eps)
        self.auc_table_size = auc_table_size
        self.auc_state = make_auc_state(auc_table_size)
        self.auc = AucCalculator(auc_table_size)
        # per-user metrics (≙ WuAucMetricMsg via MultiSlotDesc.uid_slot):
        # host-side records — opting in syncs preds per batch, exactly the
        # reference's add_uid_data D2H (metrics.cc:440)
        self.wuauc = (WuAucCalculator() if feed_config.uid_slot else None)
        self._step_fn = None
        self._packed_step_fn = None
        self._packed_sig = None
        self._packed_step_args = None   # abstract, of its first dispatch
        # a dispatch during which JAX compiled (the first after a (re)build,
        # or a silent retrace on a new shape) is compile cost, not
        # steady-state dispatch: jit.compile_s has its seconds, and it stays
        # out of trainer.step_dispatch_s (the SLO throughput-stall rule and
        # the dispatch p99 read steady-state numbers only)
        compile_cache.watch_compiles()
        self._mxu_crossing = ("take", "take")
        self._check_nan = flags.get_flags("check_nan_inf")

        if topology is not None:
            self._batch_sharding = topology.batch_sharding()
            self._replicated = topology.replicated()
        else:
            self._batch_sharding = None
            self._replicated = None

    # ------------------------------------------------------------------
    def _resolve_path(self) -> str:
        """Resolve sparse_path='auto' against the live working set; the
        concrete value is what bench/tests assert against (a silent
        fallback to a slow path must be observable)."""
        assert self.engine.ws is not None, \
            "engine pass lifecycle must run before building the step " \
            "(begin_feed_pass/add_keys/end_feed_pass/begin_pass)"
        if embedding.is_quantized(self.engine.ws):
            raise ValueError(
                "the working set is serving-frozen (int16 embedx, "
                "pull-only); training requires the f32 store — rebuild "
                "the pass (end_feed_pass/begin_pass)")
        path = self.sparse_path
        has_ex = "mf_ex" in self.engine.ws
        is_adagrad = self.engine.config.sgd.optimizer == "adagrad"
        if path == "auto":
            if has_ex and self._dym_mask is not None:
                # no path trains mf_ex under per-slot dynamic dims (fast/
                # reference pull only 3+D columns) — fail with the clear
                # error instead of an in-jit shape mismatch downstream
                raise ValueError(
                    "extended (mf_ex) tables do not compose with per-slot "
                    "dynamic mf dims — drop slot_mf_dims or the expand "
                    "embedding")
            elif self.topology is None:
                # extended (mf_ex) tables ride the mxu kernels too — the
                # ex columns join the feature-major table/payload
                path = "mxu"
            elif self._mxu_shardable():
                # explicit HeterComm-style exchange: row-sharded table,
                # all_gather(ids) + per-device sorted-SpMM kernels +
                # psum_scatter(values) inside shard_map
                # (≙ heter_comm_inl.h:1296,1730 sharded pull/push in the
                # hot loop)
                path = "mxu_sharded"
            elif has_ex:
                # fast/reference pull only 3+D columns — an extended model
                # would shape-error inside jit; demand an mxu-capable
                # layout instead of falling through
                raise ValueError(
                    "extended (mf_ex) tables need the mxu or mxu_sharded "
                    "path — this topology does not satisfy "
                    "_mxu_shardable (pure dp×sharding mesh, divisible "
                    "batch/table)")
            elif is_adagrad:
                path = "fast"
            else:
                path = "reference"
        return path

    def _mxu_shardable(self) -> bool:
        """mxu_sharded wants the HeterComm-symmetric layout: every device
        holds a batch shard AND a table shard, on a pure dp×sharding mesh
        (pp/mp/sp/ep all 1) with evenly divisible batch and table.  With
        BOTH axes > 1 the multi-node layout applies (table sharded over
        `sharding`, replicated over `dp` — topology.table_spec), so the
        table must divide by the sharding degree only."""
        if self.topology is None:
            return False
        t = self.topology
        if any(t.axis_size(a) != 1 for a in ("pp", "mp", "sp", "ep")):
            return False
        n_dev = t.axis_size("dp") * t.axis_size("sharding")
        n_tbl = (t.axis_size("sharding") if t.multinode_table() else n_dev)
        return (self.batch_size % n_dev == 0
                and self.engine.ws["show"].shape[0] % n_tbl == 0)

    def _validate_path(self, path: str) -> None:
        """Reject configs a path cannot honor — both the per-batch and the
        packed builders go through here, so an invalid explicit path raises
        instead of silently training wrong."""
        has_ex = "mf_ex" in self.engine.ws
        is_adagrad = self.engine.config.sgd.optimizer == "adagrad"
        if self._row_model and (path != "mxu" or has_ex
                                or self._dym_mask is not None
                                or self.async_dense is not None
                                or self.amp or self.wuauc is not None
                                or self.trainer_config.dump_path):
            raise ValueError(
                "a model that takes unpooled rows (row_inputs), one that "
                "ties its head to the table's rows (head_keys) too, trains on "
                "the single-device mxu path, without an expand embedding, "
                "per-slot mf dims, amp, the async dense table, per-user AUC "
                "or a prediction dump "
                f"(resolved path {path!r})")
        if path == "mxu":
            if has_ex and self._dym_mask is not None:
                raise ValueError(
                    "sparse_path='mxu' with an extended (mf_ex) table does "
                    "not compose with per-slot dynamic mf dims — drop "
                    "slot_mf_dims or the expand embedding")
        elif path == "mxu_sharded":
            if has_ex and self._dym_mask is not None:
                raise ValueError(
                    "sparse_path='mxu_sharded' with an extended (mf_ex) "
                    "table does not compose with per-slot dynamic mf dims "
                    "— drop slot_mf_dims or the expand embedding")
            if not self._mxu_shardable():
                raise ValueError(
                    "sparse_path='mxu_sharded' needs a topology with a "
                    "pure dp×sharding mesh (pp/mp/sp/ep == 1) and batch/"
                    "table sizes divisible by the device count")
        elif path == "fast":
            if not is_adagrad:
                raise ValueError(
                    "sparse_path='fast' implements the adagrad rule only "
                    f"(got {self.engine.config.sgd.optimizer!r})")
        elif path == "reference":
            if self.async_dense is not None:
                raise ValueError(
                    "dense_sync_mode='async_table' requires the mxu, "
                    "mxu_sharded or fast sparse path")
        else:
            raise ValueError(f"unknown sparse_path {path!r}")

    def _crossing_modes(self, s: int, l: int, b: int,
                        eff_p_pad: int = None, planes: bool = False):
        """Resolve the sorted<->canonical crossing lowering per direction
        (ops/crossing.py): pull's take emits p canonical rows, push's take
        emits only the trimmed width — auto mode times each on the live
        backend once per geometry.

        planes: the plan carries static payload planes, so the push
        crossing moves only the 1+D dynamic columns (gathered from the
        [B*S, 1+D] pooled-grad matrix); the pull crossing always drops the
        mf_size column (premasked in the sorted domain)."""
        from paddlebox_tpu.ops import crossing as cx
        from paddlebox_tpu.ps.mxu_path import _ex_dim
        p = s * l * b
        d = int(self.engine.ws["mf"].shape[1]) + _ex_dim(self.engine.ws)
        backend = jax.default_backend()
        dt = ("bfloat16" if flags.get_flags("mxu_crossing_bf16")
              else "float32")
        pull = cx.best_mode(p, p, 3 + d, backend, dt)
        if planes:
            push = cx.best_mode(eff_p_pad or p, p, 1 + d, backend, dt)
        else:
            # legacy payload carries the exact slot column — bf16 never
            # applies there (mxu_path.push_and_update)
            push = cx.best_mode(eff_p_pad or p, p, 4 + d, backend)
        return (pull, push)

    def _build_step(self):
        """Per-batch jitted step: takes [S, B, L] indices from the host
        packer (transposed + planned in-step)."""
        path = self._resolve_path()
        self._validate_path(path)
        if self._row_model:
            raise ValueError(
                "models that take unpooled rows require the pass-resident "
                "feed (build_pass_feed / train_pass(feed)) — the streaming "
                "per-batch path has no key planes")
        crossing = ("take", "take")
        if path == "mxu":
            crossing = self._crossing_modes(
                len(self.packer.sparse_slots), self.packer.capacity,
                self.batch_size)
        self._mxu_crossing = crossing
        core = self._make_core(path, crossing)

        def step(ws, params, opt_state, auc_state, indices, lengths, dense,
                 labels, valid, extras):
            idx_slb = jnp.transpose(indices, (0, 2, 1))    # [S, L, B]
            return core(ws, params, opt_state, auc_state, idx_slb, lengths,
                        dense, labels, valid, None, extras)

        self._step_fn = jax.jit(step, donate_argnums=(0, 1, 2, 3))

    def _pooled_dense_half(self):
        """Shared back half of the pooled-based steps (mxu/fast): dense
        fwd/bwd + dense optimizer + AUC, returning the pooled grads for the
        sparse push."""
        use_cvm = self.use_cvm
        model = self.model
        dense_tx = self.dense_tx
        amp = self.amp
        dym_mask = self._dym_mask

        apply_dense = self.async_dense is None

        def half(params, opt_state, auc_state, pooled, dense, labels, valid,
                 extras=None):
            B = pooled.shape[0]
            kw = {k: extras[k]
                  for k in getattr(model, "extra_inputs", ())} \
                if extras else {}

            def loss_fn(p, pooled_in):
                if dym_mask is not None:
                    pooled_in = pooled_in * dym_mask[None]
                x = pooled_in if use_cvm else pooled_in[:, :, 2:]
                x = x.reshape(B, -1)
                if amp:
                    p_c = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
                    logits = model.apply(
                        p_c, x.astype(jnp.bfloat16),
                        dense.astype(jnp.bfloat16), **kw).astype(jnp.float32)
                else:
                    logits = model.apply(p, x, dense, **kw)
                w = valid.astype(jnp.float32)
                per = optax.sigmoid_binary_cross_entropy(logits, labels)
                loss = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)
                return loss, jax.nn.sigmoid(logits)

            with trace.device_scope("dense.tower"):
                (loss, preds), (d_params, d_pooled) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True)(params, pooled)
            if apply_dense:
                with trace.device_scope("dense.adam"):
                    updates, opt_state = dense_tx.update(d_params, opt_state,
                                                         params)
                    params = optax.apply_updates(params, updates)
            with trace.device_scope("metrics.auc"):
                auc_state = accumulate_auc(auc_state, preds, labels, valid)
            return (params, opt_state, auc_state, loss, preds, d_pooled,
                    d_params)

        return half

    def _rows_dense_half(self):
        """The dense half for a model that owns its loss (``row_inputs``):
        it is handed the slots' unpooled rows, lengths and its extras,
        returns ``(loss, aux)``; gradients flow to the parameters and to
        the rows, the AUC accumulator is fed the model's own pairs, and
        ``aux["stats"]`` takes the place of the per-example predictions
        in the step's outputs."""
        model = self.model
        dense_tx = self.dense_tx
        tied = self._head_keys is not None
        # state of the model that takes no gradient and moves by a rule of
        # its own after Adam (a routing bias balanced by the step's counts:
        # ARCHITECTURE.md, the after-update hook)
        after_update = getattr(model, "after_update", None)

        def half(params, opt_state, auc_state, rows, lengths, valid,
                 extras, head=None):
            kw = {k: extras[k] for k in getattr(model, "extra_inputs", ())}
            if tied:
                # the head is the pulled rows of the model's head keys: a
                # third argument of the loss, and a third gradient
                (loss, aux), (d_params, d_rows, d_head) = jax.value_and_grad(
                    lambda p, x, e: model.loss(p, x, lengths, valid, head=e,
                                               **kw),
                    argnums=(0, 1, 2), has_aux=True)(params, rows, head)
            else:
                (loss, aux), (d_params, d_rows) = jax.value_and_grad(
                    lambda p, x: model.loss(p, x, lengths, valid, **kw),
                    argnums=(0, 1), has_aux=True)(params, rows)
            with trace.device_scope("dense.adam"):
                updates, opt_state = dense_tx.update(d_params, opt_state,
                                                     params)
                params = optax.apply_updates(params, updates)
            if after_update is not None:
                params = after_update(params, aux)
            with trace.device_scope("metrics.auc"):
                auc_state = accumulate_auc(auc_state, aux["auc_pred"],
                                           aux["auc_label"], aux["auc_mask"])
            return (params, opt_state, auc_state, loss, aux["stats"], d_rows,
                    d_head if tied else None)

        return half

    def _make_core(self, path: str, crossing=("take", "take")):
        """Shared per-path step body, used by BOTH the per-batch and the
        pass-resident builders (single source of step semantics).

        core(ws, params, opt_state, auc_state, idx_slb, lengths, dense,
             labels, valid, plan) -> (ws, params, opt_state, auc_state,
             loss, preds[, d_params])
        idx_slb is [S, L, B]; plan is a precomputed sorted-spmm plan for the
        mxu path (None → mask + build in-step); crossing = (pull, push)
        sorted<->canonical lowerings for the mxu path (ops/crossing.py).
        """
        sgd_cfg = self.engine.config.sgd
        use_cvm = self.use_cvm
        slot_ids = jnp.asarray(self.slot_ids)
        async_dense = self.async_dense is not None

        if path == "mxu":
            # Sorted-SpMM step (ps/mxu_path.py): the pull/push embedding
            # traffic runs as MXU one-hot matmuls instead of XLA's serial
            # gather/scatter
            from paddlebox_tpu.ps import mxu_path
            interpret = jax.default_backend() == "cpu"
            # a pooled slot's declared capacity bounds the positions the
            # pull crossing takes; unpooled rows cross the whole rectangle
            capacities = None if self._row_model else tuple(
                sl.capacity for sl in self.packer.sparse_slots)
            shape_slb = (len(self.packer.sparse_slots), self.packer.capacity,
                         self.batch_size)
            stat_set("ps.mxu.pull_cross_rows", float(
                mxu_path.pull_cross_rows(capacities, shape_slb, crossing[0])))
            stat_set("ps.mxu.pull_cross_rows_canonical",
                     float(np.prod(shape_slb)))

            def lane_gauge(ws, plan, dims, mode):
                # set while the step is traced: the layout rule reads the
                # plan's static width beside the row's
                stat_set("ps.mxu.pull_cross_lane_width", float(
                    mxu_path.pull_cross_lane_width(ws, plan, dims, mode)))

            if self._row_model:
                rows_half = self._rows_dense_half()
                tied = self._head_keys is not None

                def core(ws, params, opt_state, auc_state, idx_slb, lengths,
                         dense, labels, valid, plan, extras=None):
                    s, l, b = idx_slb.shape
                    dims = mxu_path.make_dims(s * l * b, ws["show"].shape[0])
                    if plan is None:
                        raise ValueError(
                            "a model that takes unpooled rows trains from a "
                            "feed with precomputed plans (build_pass_feed)")
                    lane_gauge(ws, plan, dims, "take")
                    with trace.device_scope("seq.pull"):
                        v = mxu_path.pull_rows(ws, plan, dims, (s, l, b),
                                               interpret=interpret)
                        rows = jax.lax.stop_gradient(
                            jnp.transpose(v[..., 3:], (2, 0, 1, 3)))
                    e = None
                    if tied:
                        head_rows = extras["head_rows"]
                        with trace.device_scope("seq.head_pull"):
                            e = jax.lax.stop_gradient(
                                mxu_path.pull_head(ws, head_rows))
                    (params, opt_state, auc_state, loss, stats, d_rows,
                     d_head) = rows_half(params, opt_state, auc_state, rows,
                                         lengths.T, valid, extras, e)
                    if tied:
                        # the rule adds what the push hands it (w += lr *
                        # ratio * g, ps/optimizer.py), and a head that is
                        # the table's rows cannot climb its own loss
                        # gradient: rows and head are pushed downhill
                        d_rows, d_head = -d_rows, -d_head
                    with trace.device_scope("seq.push"):
                        # the tower's own columns: embed_w gets no gradient,
                        # show/click the instance's counts as in every push
                        d_mf = jnp.transpose(d_rows, (1, 2, 0, 3))
                        d_occ = jnp.concatenate(
                            [jnp.zeros(d_mf.shape[:3] + (1,), d_mf.dtype),
                             d_mf], axis=-1)
                        ins_cvm = jnp.stack([jnp.ones_like(labels), labels],
                                            axis=1)
                        new_ws = mxu_path.push_and_update(
                            ws, plan, dims, idx_slb, None, ins_cvm,
                            slot_ids, sgd_cfg, interpret=interpret,
                            d_occ=d_occ,
                            head=(head_rows, d_head) if tied else None)
                    if tied:
                        # seq.head.rows / rows_applied, behind the model's
                        # own stats: the head rows read, and those whose
                        # gradient the rule applied (an occurrence touched
                        # the row, its show grew, and it was created)
                        applied = (jnp.take(new_ws["show"], head_rows)
                                   > jnp.take(ws["show"], head_rows)) \
                            & (jnp.take(ws["mf_size"], head_rows) > 0)
                        stats = jnp.concatenate([stats, jnp.stack([
                            jnp.float32(head_rows.shape[0]),
                            jnp.sum(applied).astype(jnp.float32)])])
                    return new_ws, params, opt_state, auc_state, loss, stats
                return core
            half = self._pooled_dense_half()

            def core(ws, params, opt_state, auc_state, idx_slb, lengths,
                     dense, labels, valid, plan, extras=None):
                s, l, b = idx_slb.shape
                # geometry from the *traced* working set, so per-pass table
                # resizes retrace with correct dims (and correct sentinel)
                dims = mxu_path.make_dims(s * l * b, ws["show"].shape[0])
                if plan is None:
                    # the packer parks padding at row 0 (batch_pack.py);
                    # the mask makes in-step planning safe for hand-built
                    # batches too.  Precomputed plans were built from
                    # pack_pass output, which guarantees the same.
                    idx_slb = jnp.where(jnp.arange(l)[None, :, None]
                                        < lengths[:, None, :], idx_slb, 0)
                    plan = mxu_path.build_plan(idx_slb, dims)
                lane_gauge(ws, plan, dims, crossing[0])
                pooled = jax.lax.stop_gradient(mxu_path.pull_pool_cvm(
                    ws, plan, dims, (s, l, b), use_cvm, interpret=interpret,
                    crossing=crossing[0], capacities=capacities))
                (params, opt_state, auc_state, loss, preds, d_pooled,
                 d_params) = half(params, opt_state, auc_state, pooled,
                                  dense, labels, valid, extras)
                ins_cvm = jnp.stack([jnp.ones_like(labels), labels], axis=1)
                ws = mxu_path.push_and_update(ws, plan, dims, idx_slb,
                                              d_pooled, ins_cvm, slot_ids,
                                              sgd_cfg, interpret=interpret,
                                              crossing=crossing[1])
                out = (ws, params, opt_state, auc_state, loss, preds)
                return out + ((d_params,) if async_dense else ())
            return core

        if path == "mxu_sharded":
            # the multi-chip hot loop as explicit HeterComm-equivalent
            # exchange (≙ heter_comm_inl.h:1296 pull_merge_sparse, :1730
            # push merge, :2027 gather_one_node_grad): table row-sharded in
            # contiguous blocks over every device, batch dp-sharded; pull =
            # all_gather(ids) + local sorted-SpMM gather + psum_scatter;
            # push = all_gather(ids, payload) + local sorted-SpMM merge;
            # optimizer runs GSPMD-elementwise on the row-sharded table.
            from paddlebox_tpu.ps import mxu_path
            from paddlebox_tpu.ps import sharded_embedding as se
            from jax.sharding import PartitionSpec as P
            interpret = jax.default_backend() == "cpu"
            half = self._pooled_dense_half()
            mesh = self.topology.mesh
            # multi-node layout when both axes are real: table sharded over
            # `sharding` (intra-node/ICI), replicated over `dp` (node/DCN),
            # push merges per node then psums across nodes
            # (≙ gather_one_node_grad + gather_multi_node_grad,
            # heter_comm_inl.h:2027,2131); otherwise one flat pool
            batch_axes, tbl_axes, n_tbl, _, multinode = \
                self._sharded_layout()
            tbl_spec1 = P(tbl_axes)
            tbl_spec2 = P(tbl_axes, None)

            # pull and push need the IDENTICAL sorted-SpMM plan; build it
            # ONCE per step in its own shard_map (each device's plan rides
            # a leading dim split over every device) instead of sorting
            # twice (≙ split_input_to_shard building the shard index once,
            # heter_comm_inl.h:1117)
            plan_specs = (P(batch_axes, None, None),) + (P(batch_axes),) * 7

            def core(ws, params, opt_state, auc_state, idx_slb, lengths,
                     dense, labels, valid, plan, extras=None):
                s, l, b = idx_slb.shape
                d_main = ws["mf"].shape[1]
                dx = mxu_path._ex_dim(ws)
                d = d_main + dx
                n_rows = ws["show"].shape[0]
                rows_loc = n_rows // n_tbl
                idx_slb = jnp.where(jnp.arange(l)[None, :, None]
                                    < lengths[:, None, :], idx_slb, 0)
                ex_args = (ws["mf_ex"],) if dx else ()
                ex_specs = (tbl_spec2,) if dx else ()

                if plan is not None:
                    # pass-resident per-device plans (build_pass_feed)
                    splan = plan
                else:
                    def plan_local(idx_loc):
                        _, pl = se.local_plan(idx_loc.reshape(-1), rows_loc,
                                              tbl_axes)
                        return pl

                    splan = jax.shard_map(
                        plan_local, mesh=mesh,
                        in_specs=(P(None, None, batch_axes),),
                        out_specs=plan_specs,
                        check_vma=False)(idx_slb)

                def pull_local(show, click, embed_w, mf, mf_size,
                               idx_loc, *rest):
                    mf_ex = (rest[0].T,) if dx else ()
                    pl = rest[1:] if dx else rest
                    tab = jnp.concatenate(
                        [show[None], click[None], embed_w[None], mf.T,
                         *mf_ex, mf_size.astype(jnp.float32)[None]], axis=0)
                    # multinode: the node's replica serves its own batch
                    # shard — ids/values travel over ICI only
                    vals = se.pull_rows_sharded_mxu(
                        tab, idx_loc.reshape(-1), tbl_axes,
                        interpret=interpret, plan=pl)
                    b_loc = idx_loc.shape[2]
                    return vals.T.reshape(s, l, b_loc, 3 + d + 1)

                v = jax.shard_map(
                    pull_local, mesh=mesh,
                    in_specs=(tbl_spec1, tbl_spec1, tbl_spec1, tbl_spec2,
                              tbl_spec1, P(None, None, batch_axes))
                    + ex_specs + plan_specs,
                    out_specs=P(None, None, batch_axes, None),
                    check_vma=False)(
                    ws["show"], ws["click"], ws["embed_w"], ws["mf"],
                    ws["mf_size"], idx_slb, *ex_args, *splan)
                pooled = jax.lax.stop_gradient(
                    mxu_path.pool_cvm_values(v, use_cvm))
                (params, opt_state, auc_state, loss, preds, d_pooled,
                 d_params) = half(params, opt_state, auc_state, pooled,
                                  dense, labels, valid, extras)
                ins_cvm = jnp.stack([jnp.ones_like(labels), labels], axis=1)
                payload = mxu_path.push_payload(d_pooled, ins_cvm, slot_ids,
                                                (s, l, b))   # [S,L,B,D+4]

                def push_local(idx_loc, pay_loc, *pl):
                    p_loc = idx_loc.size
                    pay_fm = pay_loc.reshape(p_loc, d + 4).T  # [D+4, P_loc]
                    if multinode:
                        return se.push_rows_sharded_mxu_multinode(
                            idx_loc.reshape(-1), pay_fm, rows_loc,
                            tbl_axes, "dp", interpret=interpret,
                            first_only_col=d + 3, plan=pl)
                    return se.push_rows_sharded_mxu(
                        idx_loc.reshape(-1), pay_fm, rows_loc, tbl_axes,
                        interpret=interpret, first_only_col=d + 3, plan=pl)

                delta = jax.shard_map(
                    push_local, mesh=mesh,
                    in_specs=(P(None, None, batch_axes),
                              P(None, None, batch_axes, None)) + plan_specs,
                    out_specs=P(None, tbl_axes),
                    check_vma=False)(idx_slb, payload, *splan)  # [D+4, n_rows]
                with trace.device_scope("ps.push.rule"):
                    acc = mxu_path.acc_from_delta(delta, n_rows,
                                                  d_main=d_main)
                    ws = sparse_opt.apply_push(ws, acc, sgd_cfg)
                out = (ws, params, opt_state, auc_state, loss, preds)
                return out + ((d_params,) if async_dense else ())
            return core

        if path == "fast":
            # tiling-aware step (ps/fast_path.py docstring); numerically
            # identical to the reference step — tests/test_fast_path.py
            from paddlebox_tpu.ps import fast_path
            half = self._pooled_dense_half()

            def core(ws, params, opt_state, auc_state, idx_slb, lengths,
                     dense, labels, valid, plan, extras=None):
                prelude = fast_path.step_prelude(idx_slb, lengths)
                pooled = jax.lax.stop_gradient(
                    fast_path.pull_pool_cvm(ws, idx_slb, lengths, use_cvm,
                                            prelude=prelude))
                (params, opt_state, auc_state, loss, preds, d_pooled,
                 d_params) = half(params, opt_state, auc_state, pooled,
                                  dense, labels, valid, extras)
                ins_cvm = jnp.stack([jnp.ones_like(labels), labels], axis=1)
                ws = fast_path.push_and_update(ws, idx_slb, lengths,
                                               d_pooled, ins_cvm, slot_ids,
                                               sgd_cfg, prelude=prelude)
                out = (ws, params, opt_state, auc_state, loss, preds)
                return out + ((d_params,) if async_dense else ())
            return core

        model, dense_tx, amp = self.model, self.dense_tx, self.amp
        dym_mask = self._dym_mask

        def core(ws, params, opt_state, auc_state, idx_slb, lengths, dense,
                 labels, valid, plan, extras=None):
            indices = jnp.transpose(idx_slb, (0, 2, 1))    # [S, B, L]
            # 1. pull (≙ PullSparseCaseGPU box_wrapper_impl.h:25)
            emb = jax.lax.stop_gradient(embedding.pull_sparse(ws, indices))
            ins_cvm = jnp.stack([jnp.ones_like(labels), labels], axis=1)
            kw = {k: extras[k]
                  for k in getattr(model, "extra_inputs", ())} \
                if extras else {}

            # 2-3. forward + backward over (dense params, pulled embeddings)
            def loss_fn(p, e):
                pooled = fused_seqpool_cvm(e, lengths, ins_cvm, use_cvm)
                if dym_mask is not None:
                    # fused_seqpool_cvm emits [B, S*E] flattened; with
                    # use_cvm=False the 2 cvm columns are dropped first
                    m = dym_mask if use_cvm else dym_mask[:, 2:]
                    pooled = pooled * m.reshape(-1)[None]
                if amp:
                    # bf16 compute, f32 master weights (strategy.amp —
                    # ≙ fleet amp meta-optimizer; MXU runs 2x+ in bf16)
                    p_c = jax.tree.map(
                        lambda a: a.astype(jnp.bfloat16), p)
                    logits = model.apply(
                        p_c, pooled.astype(jnp.bfloat16),
                        dense.astype(jnp.bfloat16), **kw).astype(jnp.float32)
                else:
                    logits = model.apply(p, pooled, dense, **kw)
                w = valid.astype(jnp.float32)
                per = optax.sigmoid_binary_cross_entropy(logits, labels)
                loss = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)
                return loss, jax.nn.sigmoid(logits)

            (loss, preds), (d_params, d_emb) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, emb)

            # 4-6. push + sparse optimizer (≙ PushSparseGradCaseGPU +
            # SparseAdagrad, box_wrapper_impl.h:373, optimizer.cuh.h:31)
            acc = embedding.push_sparse_grads(ws, indices, d_emb, slot_ids)
            ws = sparse_opt.apply_push(ws, acc, sgd_cfg)

            # dense update (≙ SyncDense/async dense table,
            # boxps_worker.cc:1191-1253 — implicit psum via GSPMD)
            updates, opt_state = dense_tx.update(d_params, opt_state, params)
            params = optax.apply_updates(params, updates)

            # 7. metrics on device (≙ AddAucMonitor boxps_worker.cc:1337)
            auc_state = accumulate_auc(auc_state, preds, labels, valid)
            return ws, params, opt_state, auc_state, loss, preds

        return core

    # ------------------------------------------------------------------
    # pass-resident path (≙ SlotPaddleBoxDataFeed whole-pass GPU pack,
    # data_feed.h:2036 + data_feed.cu:1210-1318): the step takes a batch
    # INDEX and dynamic-slices device-resident stacked arrays; plans for
    # the mxu path are precomputed at pass-build time, so the hot step
    # contains no sorts and no host work at all.
    @trace.span("trainer.pack_pass_host")
    def pack_pass_host(self, dataset: SlotDataset, mapper=None,
                       on_plane=None) -> "pass_feed.HostPassArrays":
        """Host half of :meth:`build_pass_feed`: pack + translate the
        whole pass into SoA planes.  No device dispatch (unless the caller
        passes an ``on_plane`` stager) and no dependence on the ADOPTED
        working set, nor on any pulled row: it reads only the mapper's
        sorted keys.  With an explicit ``mapper`` (e.g.
        ``engine.peek_next_mapper()``) the prefetcher runs this on a
        background thread while the engine's build thread still pulls
        the pass's rows and the previous pass still trains."""
        from paddlebox_tpu.data import pass_feed as pf
        self._require_pv_for_rank(dataset)
        label = (self.packer.label_slots
                 if len(self.packer.label_slots) > 1 else self.packer.label_slot)
        # pv-grouped datasets batch on page-view boundaries (a pv trains as
        # one unit, ≙ PadBoxSlotDataset whole-pv batches) — hand the pass
        # pack the cut COUNTS over the merged order (batch_bounds copies no
        # slot data; slicing + re-concatenating blocks would copy the pass
        # twice)
        counts = None
        if getattr(dataset, "_pv_grouped", False):
            counts = [hi - lo
                      for lo, hi in dataset.batch_bounds(self.batch_size)]
        arrays = pf.pack_pass(dataset.get_blocks(), self.packer.config,
                              self.batch_size, label,
                              key_mapper=(self.engine.mapper if mapper is None
                                          else mapper),
                              batch_counts=counts, on_plane=on_plane,
                              seq_key_slot=self._seq_key_slot,
                              head_keys=self._head_keys,
                              planes=self._plane_store)
        return arrays

    def pass_shardings(self, arrays) -> Optional[dict]:
        """The resident pass's target shardings under a topology (batch
        dims dp-wise, mirroring _put_batch) — None single-device."""
        if self.topology is None:
            return None
        t = self.topology
        dp = ("dp", "sharding")
        shardings = {
            "indices": t.sharding(None, None, None, dp),  # [N,S,L,B]
            "lengths": t.sharding(None, None, dp),        # [N,S,B]
            "dense": t.sharding(None, dp, None),          # [N,B,D]
            "labels": (t.sharding(None, dp) if arrays.labels.ndim == 1
                       else t.sharding(None, dp, None)),
            "valid": t.sharding(None, dp),
        }
        for k in arrays.extra_planes():
            shardings[k] = t.sharding(None, dp, None)
        return shardings

    @trace.span("trainer.finish_pass_feed")
    def finish_pass_feed(self, arrays, keep_host: bool = False,
                         staged=None) -> PackedPassFeed:
        """Device half of :meth:`build_pass_feed`: upload + relayout the
        packed planes and (mxu paths) precompute per-batch plans.  Needs
        the pass's working set ADOPTED (plan dims read ws height), so the
        prefetcher calls this on the MAIN thread right after
        engine.begin_pass()."""
        from paddlebox_tpu.data import pass_feed as pf
        assert self.engine.ws is not None, "engine lifecycle must run first"
        keep = keep_host or bool(self.trainer_config.dump_path)
        feed = pf.upload_pass(arrays, keep_host=keep,
                              sharding=self.pass_shardings(arrays),
                              staged=staged)
        with trace.span("data.feed.plans"):
            path = self._resolve_path()
            if path == "mxu":
                from paddlebox_tpu.ops import sorted_spmm as sp
                from paddlebox_tpu.ps import mxu_path
                n, s, l, b = feed.data["indices"].shape
                dims = mxu_path.make_dims(s * l * b,
                                          self.engine.ws["show"].shape[0])
                # padding occurrences (row 0) are dead kernel work — trim the
                # plans to the widest batch's real-occurrence count (host
                # lengths are exact, so this is a static bound for the pass)
                per_batch = arrays.lengths.reshape(s, n, b).sum(axis=(0, 2))
                eff = sp.trimmed_dims(dims, int(per_batch.max()))
                pf.precompute_plans(feed, dims, eff, slot_ids=self.slot_ids)
            elif path == "mxu_sharded":
                self._precompute_sharded_plans(feed)
        return feed

    def build_pass_feed(self, dataset: SlotDataset,
                        keep_host: bool = False) -> PackedPassFeed:
        """Pack + translate + upload the whole pass, and (mxu path)
        precompute the per-batch sorted-spmm plans.  Runs at pass-build
        time — the train loop then touches no per-batch host work.
        Composition of pack_pass_host + finish_pass_feed (the prefetcher
        drives the halves on separate threads)."""
        assert self.engine.ws is not None, "engine lifecycle must run first"
        arrays = self.pack_pass_host(dataset)
        return self.finish_pass_feed(arrays, keep_host=keep_host)

    def _sharded_layout(self):
        """(batch_axes, tbl_axes, n_tbl, rows_loc, multinode) of the
        mxu_sharded exchange — single source for the core, the pass-plan
        builder and the stale-plan check."""
        batch_axes = ("dp", "sharding")
        multinode = self.topology.multinode_table()
        tbl_axes = ("sharding",) if multinode else batch_axes
        n_tbl = 1
        for a in tbl_axes:
            n_tbl *= self.topology.axis_size(a)
        n_rows = self.engine.ws["show"].shape[0]
        return batch_axes, tbl_axes, n_tbl, n_rows // n_tbl, multinode

    def _precompute_sharded_plans(self, feed: PackedPassFeed) -> None:
        """Pass-resident per-device exchange plans: each device's localized
        sorted-SpMM plan for every batch, built once at pass build (the
        multi-chip twin of precompute_plans — the hot step then contains
        no sorts on ANY path; ≙ the pass-scope shard index of
        split_input_to_shard, heter_comm_inl.h:1117).

        Footprint: plans are UNTRIMMED (sharded exchanges localize ids
        per device, so padding does not sort to a droppable prefix) and
        scale as n_batches x n_devices x gathered-P — the byte count is
        logged; chunked residency is the escape hatch if a pass outgrows
        HBM (split the pass into several feeds)."""
        batch_axes, tbl_axes, n_tbl, rows_loc, _ = self._sharded_layout()
        build = _sharded_plan_builder(self.topology.mesh, batch_axes,
                                      tbl_axes, rows_loc)
        pl = build(feed.data["indices"])
        feed.plans = {"rows2d": pl[0], "perm": pl[1], "inv_perm": pl[2],
                      "ch": pl[3], "tl": pl[4], "fg": pl[5], "fs": pl[6],
                      "first_occ": pl[7]}
        feed.plan_dims = self._sharded_plan_key(feed)
        import logging
        logging.getLogger(__name__).info(
            "sharded pass plans resident: %.0f MB global "
            "(n_batches x n_devices x gathered-P, untrimmed)",
            sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in feed.plans.values()) / 1e6)

    def _sharded_plan_key(self, feed: PackedPassFeed):
        """Identity of the exchange geometry sharded plans were built
        for (feed shape, table height, tbl axes layout) — any change makes
        resident plans silently corrupting, so the packed loop compares
        this before every pass."""
        _, tbl_axes, n_tbl, _, _ = self._sharded_layout()
        return ("mxu_sharded", tuple(feed.data["indices"].shape),
                self.engine.ws["show"].shape[0], tbl_axes, n_tbl)

    def _require_pv_for_rank(self, dataset) -> None:
        """rank_offset is only meaningful when every batch holds WHOLE page
        views (the reference emits it exclusively under pv merge) — a pv
        split across dense batch cuts would silently see only its
        fragment's peers, so refuse loudly instead."""
        if (self.packer.config.rank_offset
                or self.packer.config.ads_offset) \
                and not getattr(dataset, "_pv_grouped", False):
            raise ValueError(
                "DataFeedConfig(rank_offset/ads_offset) requires "
                "pv-grouped batches — call dataset.preprocess_instance() "
                "before training (≙ GetRankOffset's whole-pv batches, "
                "data_feed.cc:1855)")

    def _packed_signature(self, feed: PackedPassFeed):
        """Trace-structural key of the packed step for a feed: path, plan
        presence, async flag, crossing modes, table height, feed geometry.
        Shared by the builder and the train loop so a stale comparison can
        never skip (or force) a rebuild."""
        path = self._resolve_path()
        with_plans = feed.plans is not None
        n, s, l, b = feed.data["indices"].shape
        exch_bf16 = (flags.get_flags("sharded_exchange_bf16")
                     if path == "mxu_sharded" else False)
        crossing = ("take", "take")
        planes = with_plans and "bs" in feed.plans
        if path == "mxu" and not self._row_model:
            # (unpooled rows cross by take: a sort has one operand a column)
            eff_p_pad = None
            if with_plans:
                r = feed.plans["rows2d"].shape      # [N, n_chunks, 1, c]
                eff_p_pad = int(r[1]) * int(r[3])
            crossing = self._crossing_modes(s, l, b, eff_p_pad, planes)
        cross_bf16 = bool(flags.get_flags("mxu_crossing_bf16"))
        return (path, with_plans, self.async_dense is not None, crossing,
                exch_bf16, self.engine.ws["show"].shape[0], (n, s, l, b),
                planes, cross_bf16)

    def _build_packed_step(self, feed: PackedPassFeed):
        """Thin wrapper over the same per-path core as _build_step: slice
        the resident arrays (and the precomputed plan) by batch index."""
        sig = self._packed_signature(feed)
        path, with_plans, _, crossing = sig[:4]
        self._validate_path(path)
        self._mxu_crossing = crossing
        core = self._make_core(path, crossing)

        def step(ws, params, opt_state, auc_state, i, data, plans):
            with trace.device_scope("feed.slice"):
                # batch i of every pass plane, cut at the top behind one
                # barrier: the pull reads the plan slices, so it waits for
                # the dense slice too, and a whole-pass plane the compiler
                # keeps in fast memory for that slice is handed back
                # before the pull's crossing needs the room.  A plane the
                # body never reads stays an argument; the compiled step
                # drops its slice
                bt, pl = jax.lax.optimization_barrier(
                    slice_batch((data, plans), i))
                plan = plan_tuple(pl) if with_plans else None
            extras = {k: bt[k] for k in bt
                      if k not in ("indices", "lengths", "dense", "labels",
                                   "valid")}
            return core(ws, params, opt_state, auc_state, bt["indices"],
                        bt["lengths"], bt["dense"], bt["labels"],
                        bt["valid"], plan, extras)

        self._packed_step_fn = jax.jit(step, donate_argnums=(0, 1, 2, 3))
        self._packed_step_args = None
        # n_rows + feed geometry drive retrace via shapes, but the plan
        # presence/path/async/crossing flags are trace-structural — key them
        self._packed_sig = sig

    def step_lowered(self):
        """The packed step as it last ran, lowered again from the abstract
        arguments of its first dispatch (shapes, dtypes, shardings: no
        array is held): the one way the program and its tools print the
        step.  ``.as_text()`` is the StableHLO with the Mosaic kernels'
        names; ``.compile()`` answers from the compile cache and its
        ``.as_text()`` holds the instructions a profiler trace names."""
        if self._packed_step_args is None:
            raise ValueError("no packed step has been dispatched yet "
                             "(train_pass(feed) notes its arguments)")
        return self._packed_step_fn.lower(*self._packed_step_args)

    def step_scopes(self) -> Dict[str, str]:
        """Instruction name -> ``op_name`` of the compiled packed step:
        which ``trace.DEVICE_SCOPES`` each instruction lies under."""
        return trace.instruction_scopes(
            self.step_lowered().compile().as_text())

    def _train_packed(self, feed: PackedPassFeed,
                      progress=None) -> Dict[str, float]:
        """Device-resident train loop: per-batch host work is one int32
        dispatch (≙ the reference train loop consuming pre-packed GPU
        batches, data_feed.h:519 MiniBatchGpuPack)."""
        path = self._resolve_path()
        async_dense = self.async_dense is not None
        if feed.plans is not None and path == "mxu":
            # plans encode the table geometry (sentinel tile, worklist);
            # a cross-pass resize makes them silently corrupting, not just
            # stale — refuse and demand a rebuilt feed
            from paddlebox_tpu.ps import mxu_path
            n, s, l, b = feed.data["indices"].shape
            cur = mxu_path.make_dims(s * l * b,
                                     self.engine.ws["show"].shape[0])
            if cur != feed.plan_dims:
                raise ValueError(
                    "PackedPassFeed plans were built for table dims "
                    f"{feed.plan_dims}, but the working set now needs "
                    f"{cur} — rebuild the feed (build_pass_feed) after a "
                    "table resize")
        elif feed.plans is not None and path == "mxu_sharded":
            cur = self._sharded_plan_key(feed)
            if cur != feed.plan_dims:
                raise ValueError(
                    "PackedPassFeed sharded plans were built for "
                    f"{feed.plan_dims}, but the exchange now needs {cur} — "
                    "rebuild the feed (build_pass_feed) after a table or "
                    "mesh change")
        if self._packed_step_fn is None \
                or self._packed_sig != self._packed_signature(feed):
            self._build_packed_step(feed)
        if self.wuauc is not None and (feed.uid is None
                                       or feed.host_labels is None):
            raise ValueError(
                "uid_slot is configured but this feed carries no host "
                "uids/labels — build it with build_pass_feed")
        engine = self.engine
        ws, params = engine.ws, self.params
        opt_state, auc_state = self.opt_state, self.auc_state
        plans = feed.plans if feed.plans is not None else {}
        if self._packed_step_args is None:      # once a build, not a step
            # a committed array's sharding is part of the lowering, an
            # uncommitted one's is not: step_lowered() must key the same
            self._packed_step_args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding
                    if getattr(a, "committed", False) else None),
                (ws, params, opt_state, auc_state, np.int32(0), feed.data,
                 plans))
        losses = []
        row_stats = []     # a row model's per-step stats (its "preds")
        n_batches = 0
        dump_file = None
        if self.trainer_config.dump_path:
            if feed.host is None:
                raise ValueError(
                    "dump_path requires build_pass_feed(keep_host=True)")
            import os
            os.makedirs(self.trainer_config.dump_path, exist_ok=True)
            dump_file = open(
                f"{self.trainer_config.dump_path}/dump-pass-"
                f"{self.engine.pass_id}.txt", "w")
        try:
            with trace.span("trainer.dispatch_steps",
                            steps=feed.n_batches):
                for i in range(feed.n_batches):
                    t_step = time.perf_counter()
                    m_step = time.monotonic()
                    compiled = compile_cache.compile_requests
                    with self.timers("step"):
                        out = self._packed_step_fn(ws, params, opt_state,
                                                   auc_state, np.int32(i),
                                                   feed.data, plans)
                    # device-busy window for feed-gap attribution (dispatch
                    # window; on async backends the device may still be
                    # executing past it — a lower bound, not an overcount)
                    intervals.record("device", m_step, time.monotonic())
                    # per-batch dispatch latency distribution (the loss
                    # readback below is the sync point, so this is dispatch
                    # cost, not device step time); a dispatch that compiled
                    # is in jit.compile_s instead
                    if compile_cache.compile_requests == compiled:
                        stat_observe("trainer.step_dispatch_s",
                                     time.perf_counter() - t_step)
                    if async_dense:
                        (ws, params, opt_state, auc_state, loss, preds,
                         d_params) = out
                        self.async_dense.push(d_params)
                        if (i + 1) % max(
                                self.trainer_config.sync_weight_step, 1) == 0:
                            params = jax.device_put(self.async_dense.pull())
                    else:
                        ws, params, opt_state, auc_state, loss, preds = out
                    if self._check_nan and not np.isfinite(float(loss)):
                        raise FloatingPointError(f"NaN/Inf loss at batch {i}")
                    if dump_file is not None:
                        h = feed.host
                        lo, cnt, base = h.real_range(i)
                        if cnt:
                            p = np.asarray(preds)[:cnt]
                            lbl = np.asarray(h.labels[lo:lo + cnt])
                            ids = (h.ins_ids[base:base + cnt] if h.ins_ids
                                   else [""] * cnt)
                            for j in range(cnt):
                                dump_file.write(
                                    f"{ids[j]}\t{lbl[j]:g}\t{p[j]:.6f}\n")
                    if self.wuauc is not None:
                        sl = slice(i * feed.batch_size,
                                   (i + 1) * feed.batch_size)
                        lbl = feed.host_labels[sl]
                        if lbl.ndim > 1:
                            lbl = lbl[:, 0]
                        self.wuauc.add_data(np.asarray(preds), lbl,
                                            feed.uid[sl], feed.host_valid[sl])
                    losses.append(loss)
                    if self._row_model:
                        row_stats.append(preds)
                    n_batches += 1
                    if progress is not None:
                        progress(n_batches)
        finally:
            if dump_file is not None:
                dump_file.close()
            self._save_state(ws, params, opt_state, auc_state)
        if async_dense:
            self.async_dense.drain()
            self.params = jax.device_put(self.async_dense.pull())
        with trace.span("trainer.readback"):
            out = self._finalize_metrics(self.auc_state)
            out["batches"] = n_batches
            # one stacked device->host sync, not one RPC per batch scalar
            per_step = np.asarray(jnp.stack(losses)) if losses \
                else np.zeros((0,), np.float32)
            if row_stats:
                total = np.asarray(jnp.sum(jnp.stack(row_stats), axis=0))
                if self._head_keys is not None:
                    stat_add("seq.head.rows", float(total[-2]))
                    stat_add("seq.head.rows_applied", float(total[-1]))
                    total = total[:-2]
                self.model.record_stats(total, n_batches)
        # the losses are read, so the pass has trained: every transfer
        # from the feed's host planes completed and the relayout consumed
        # them — the next pack may write them (ARCHITECTURE.md, planes)
        self._plane_store.give_back(feed.storage)
        feed.storage = None
        out["loss"] = float(per_step.mean()) if losses else float("nan")
        out["losses"] = [float(x) for x in per_step]
        return out

    def _save_state(self, ws, params, opt_state, auc_state):
        """The step donates ws/params/opt/auc buffers, so the objects held
        at entry are dead after the first step — save the latest state even
        on failure, or the engine is left pointing at deleted buffers.  A
        failure inside the step may have consumed (donated) its inputs with
        no output produced: save each state group only if its buffers are
        still alive, else None — later use then fails with a clear
        lifecycle error (rebuild the pass / reload the checkpoint), not a
        cryptic deleted-buffer crash."""
        def _alive(tree):
            return all(not (hasattr(leaf, "is_deleted") and leaf.is_deleted())
                       for leaf in jax.tree.leaves(tree))

        self.engine.ws = ws if _alive(ws) else None
        self.params = params if _alive(params) else None
        self.opt_state = opt_state if _alive(opt_state) else None
        self.auc_state = auc_state if _alive(auc_state) else None

    # ------------------------------------------------------------------
    def _put_batch(self, batch: PackedBatch):
        arrs = (batch.indices, batch.lengths, batch.dense, batch.labels,
                batch.valid)
        extras = {}
        if batch.rank_offset is not None:
            extras["rank_offset"] = batch.rank_offset
        if batch.aux:
            extras.update(batch.aux)
        repl_extras = {}
        if batch.ads_offset is not None:
            repl_extras["ads_offset"] = batch.ads_offset
        if self._batch_sharding is None:
            ex = {k: jnp.asarray(v) for k, v in extras.items()}
            ex.update({k: jnp.asarray(v) for k, v in repl_extras.items()})
            return tuple(jnp.asarray(a) for a in arrs) + (ex,)
        out = []
        for i, a in enumerate(arrs):
            if i == 0:  # [S,B,L] — batch dim 1
                sh = self.topology.sharding(None, ("dp", "sharding"), None)
            elif i == 1:
                sh = self.topology.sharding(None, ("dp", "sharding"))
            else:
                sh = self._batch_sharding
            out.append(jax.device_put(a, sh))
        ex_sh = self.topology.sharding(("dp", "sharding"), None)
        ex = {k: jax.device_put(v, ex_sh) for k, v in extras.items()}
        ex.update({k: jax.device_put(v, self._replicated)
                   for k, v in repl_extras.items()})
        return tuple(out) + (ex,)

    def train_pass(self, dataset: SlotDataset, prefetch: int = 4,
                   pack_threads: int = 1,
                   progress=None) -> Dict[str, float]:
        """Run one full pass over the dataset (≙ TrainFiles loop).

        Packing runs in background threads feeding a bounded channel so the
        device step overlaps with host batch assembly.  pack_threads > 1
        fans batch assembly over a thread pool (numpy releases the GIL)
        while the bounded channel of ordered futures preserves batch order
        (≙ the per-device PackBatchTask threads, boxps_worker.cc:1259).

        progress, if given, is called as progress(n_batches_done) after
        every device step — bench/driver heartbeat hook.

        A PackedPassFeed (build_pass_feed) routes to the device-resident
        loop instead — zero per-batch host work.
        """
        t0 = time.perf_counter()
        with trace.span("trainer.train_pass", pass_id=self.engine.pass_id):
            if isinstance(dataset, PackedPassFeed):
                stats = self._train_packed(dataset, progress)
            else:
                stats = self._train_stream(dataset, prefetch, pack_threads,
                                           progress)
        dt = time.perf_counter() - t0
        # "train" seconds land in the ENGINE's registry so the per-pass
        # PrintSyncTimer report shows pull/train/write side by side
        self.engine.timers.add("train", dt)
        if getattr(self.engine, "cache", None) is not None:
            # this pass's HBM-tier hit rate (set at adoption) rides along
            # with the training metrics for drivers like fleet/bench
            stats["cache_hit_rate"] = stat_snapshot("ps.cache.").get(
                "ps.cache.hit_rate", 0.0)
        return stats

    def _train_stream(self, dataset: SlotDataset, prefetch: int,
                      pack_threads: int, progress) -> Dict[str, float]:
        """Per-batch host-pack path of train_pass (streaming datasets)."""
        self._require_pv_for_rank(dataset)
        if self._step_fn is None:
            self._build_step()
        engine = self.engine
        assert engine.ws is not None, "call engine lifecycle first"
        mapper = engine.mapper
        ch = Channel(capacity=prefetch)

        import concurrent.futures
        pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, pack_threads),
            thread_name_prefix="pbox-pack")

        def pack_one(block):
            t0 = time.perf_counter()
            m0 = time.monotonic()
            b = self.packer.pack(block, key_mapper=mapper)
            intervals.record("pack", m0, time.monotonic())
            self.timers.add("pack", time.perf_counter() - t0)
            return b

        def packer_thread():
            try:
                for block in dataset.batches(self.batch_size):
                    if not ch.put(pool.submit(pack_one, block)):
                        break  # consumer closed the channel (failed pass)
            finally:
                ch.close()

        t = threading.Thread(target=packer_thread, daemon=True)
        t.start()

        ws, params = engine.ws, self.params
        opt_state, auc_state = self.opt_state, self.auc_state
        n_batches = 0
        losses = []
        dump_file = None
        if self.trainer_config.dump_path:
            # ≙ TrainerDesc dump_fields/dump_path (trainer_desc.proto:38-40,
            # DumpWorkField): per-instance "ins_id\tlabel\tpred" lines
            import os
            os.makedirs(self.trainer_config.dump_path, exist_ok=True)
            dump_file = open(
                f"{self.trainer_config.dump_path}/dump-pass-"
                f"{self.engine.pass_id}.txt", "w")
        try:
            with trace.span("trainer.dispatch_steps"):
                while True:
                    try:
                        batch = ch.get().result()
                    except ChannelClosed:
                        break
                    dev = self._put_batch(batch)
                    t_step = time.perf_counter()
                    m_step = time.monotonic()
                    compiled = compile_cache.compile_requests
                    with self.timers("step"):
                        out = self._step_fn(ws, params, opt_state, auc_state,
                                            *dev)
                    intervals.record("device", m_step, time.monotonic())
                    # same per-batch dispatch distribution as the packed loop:
                    # the SLO watchdog's throughput-stall rule rates this
                    # counter, so BOTH train paths must feed it — and both
                    # leave a dispatch that compiled to jit.compile_s
                    if compile_cache.compile_requests == compiled:
                        stat_observe("trainer.step_dispatch_s",
                                     time.perf_counter() - t_step)
                    if self.async_dense is not None:
                        (ws, params, opt_state, auc_state, loss, preds,
                         d_params) = out
                        # ≙ PushDense (boxps_worker.cc:252): grads to the
                        # table
                        self.async_dense.push(d_params)
                        if (n_batches + 1) % max(
                                self.trainer_config.sync_weight_step, 1) == 0:
                            # ≙ PullDense snapshot refresh
                            # (boxps_worker.cc:1301)
                            params = jax.device_put(self.async_dense.pull())
                    else:
                        ws, params, opt_state, auc_state, loss, preds = out
                    if self._check_nan and not np.isfinite(float(loss)):
                        raise FloatingPointError(
                            f"NaN/Inf loss at batch {n_batches}")
                    if dump_file is not None:
                        p = np.asarray(preds)[:batch.num_real]
                        lbl = batch.labels[:batch.num_real]
                        ids = batch.ins_ids or [""] * batch.num_real
                        for i in range(batch.num_real):
                            dump_file.write(
                                f"{ids[i]}\t{lbl[i]:g}\t{p[i]:.6f}\n")
                    if self.wuauc is not None:
                        lblh = (batch.labels if batch.labels.ndim == 1
                                else batch.labels[:, 0])
                        self.wuauc.add_data(np.asarray(preds), lblh,
                                            batch.uid, batch.valid)
                    losses.append(loss)
                    n_batches += 1
                    if progress is not None:
                        progress(n_batches)
        finally:
            # on any exit — including a pack-future exception or the NaN
            # guard — unblock the producer (close is idempotent; its own
            # finally also closes), reap it, cancel queued packs, and never
            # leak the dump file across failed passes
            ch.close()
            t.join()
            pool.shutdown(wait=False, cancel_futures=True)
            if dump_file is not None:
                dump_file.close()
            self._save_state(ws, params, opt_state, auc_state)
        if self.async_dense is not None:
            self.async_dense.drain()
            params = jax.device_put(self.async_dense.pull())
            self.params = params

        with trace.span("trainer.readback"):
            out = self._finalize_metrics(auc_state)
            out["batches"] = n_batches
            # one stacked device->host sync, not one RPC per batch scalar
            out["loss"] = float(jnp.mean(jnp.stack(losses))) \
                if losses else float("nan")
        return out

    def _finalize_metrics(self, auc_state) -> Dict[str, float]:
        self.auc.reset()
        self.auc.merge_device_state(jax.device_get(auc_state))
        out = self.auc.compute()
        # compact folded pos/neg export: the windowed-AUC / PSI-drift
        # monitors (metrics/quality.py) retain this across passes instead
        # of the 1M-bucket tables
        pos, neg = self.auc.folded_buckets()
        out["auc_buckets"] = {"pos": pos.tolist(), "neg": neg.tolist()}
        if self.wuauc is not None:
            w = self.wuauc.compute()
            out["uauc"] = w["uauc"]
            out["wuauc"] = w["wuauc"]
            out["wuauc_users"] = w["user_cnt"]
            # per-pass metric: drop the raw records (≙ reset_records) —
            # unlike the O(table_size) AUC buckets they grow per record
            self.wuauc.reset()
        return out

    def reset_metrics(self):
        self.auc_state = make_auc_state(self.auc_table_size)
        self.auc.reset()
        if self.wuauc is not None:
            self.wuauc.reset()


@lru_cache(maxsize=None)
def _sharded_plan_builder(mesh, batch_axes, tbl_axes, rows_loc: int):
    """Cached jitted pass-plan builder (one trace per exchange geometry —
    a fresh jit per pass would re-trace the shard_map'd sort pipeline at
    every pass build)."""
    from jax.sharding import PartitionSpec as P
    from paddlebox_tpu.ps import sharded_embedding as se
    plan_specs = (P(batch_axes, None, None),) + (P(batch_axes),) * 7

    @jax.jit
    def build(idx_all):
        def one(idx_slb):
            def plan_local(idx_loc):
                _, pl = se.local_plan(idx_loc.reshape(-1), rows_loc,
                                      tbl_axes)
                return pl
            return jax.shard_map(
                plan_local, mesh=mesh,
                in_specs=(P(None, None, batch_axes),),
                out_specs=plan_specs, check_vma=False)(idx_slb)
        return jax.lax.map(one, idx_all)

    return build
