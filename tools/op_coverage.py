"""Op-coverage inventory: reference op registry vs paddle_tpu.

The reference registers 351 op types via REGISTER_OPERATOR in
/root/reference/paddle/fluid/operators (349 distinct names; 119 are *_grad
pairs that JAX autodiff subsumes, one is the literal macro parameter
`op_type`). This tool maps every forward op to its paddle_tpu equivalent
and emits OPS_COVERAGE.md.

Statuses:
- impl:      implemented — the symbol listed exists (verified by import)
- inherent:  capability native to JAX/XLA/jnp (autodiff, cast, shape, ...)
- design:    deliberately replaced by a TPU-idiomatic design documented in
             SURVEY.md (LoD -> ragged/segment ids, RPC pserver ->
             sharded params + collectives, fusion ops -> XLA fusion, ...)
- excluded:  backend-specific machinery with no TPU meaning (mkldnn,
             ngraph, tensorrt engines, CSP go op)
- missing:   not yet built

Run: python tools/op_coverage.py  (writes OPS_COVERAGE.md, prints summary;
--check exits nonzero if any `impl` symbol fails to resolve).
"""

from __future__ import annotations

import importlib
import sys
from collections import Counter

import _bootstrap  # noqa: F401  (repo path)

# (ref_op, status, paddle_tpu symbol or rationale)
TABLE = [
    ("accuracy", "impl", "metrics.accuracy / metrics.Accuracy"),
    ("add_position_encoding", "impl", "ops.extras.add_position_encoding"),
    ("affine_channel", "impl", "ops.extras.affine_channel"),
    ("affine_grid", "impl", "ops.extras.affine_grid"),
    ("anchor_generator", "impl", "ops.detection.anchor_generator"),
    ("arg_max", "inherent", "jnp.argmax (exported via ops.functional)"),
    ("arg_min", "inherent", "jnp.argmin"),
    ("argsort", "impl", "ops.functional.argsort"),
    ("array_to_lod_tensor", "design",
     "tensor-array ops -> lax.scan carries (SURVEY §7: LoD -> segment ids)"),
    ("assign", "inherent", "functional assignment (jnp.asarray/copy)"),
    ("assign_value", "inherent", "jnp.asarray"),
    ("attention_lstm", "design",
     "fused op -> XLA fusion of nn.rnn.LSTMCell + kernels.attention"),
    ("average_accumulates", "impl", "optim.optimizer.ModelAverage"),
    ("batch_norm", "impl", "nn.layers.BatchNorm"),
    ("beam_search", "impl", "ops.beam_search.beam_search"),
    ("beam_search_decode", "impl", "ops.beam_search.BeamResult backtrace"),
    ("bilinear_interp", "impl", "ops.functional.resize_bilinear"),
    ("bilinear_tensor_product", "impl",
     "ops.extras.bilinear_tensor_product"),
    ("bipartite_match", "impl", "ops.detection.bipartite_match"),
    ("box_clip", "impl", "ops.detection.box_clip"),
    ("box_coder", "impl", "ops.detection.box_coder"),
    ("bpr_loss", "impl", "ops.extras.bpr_loss"),
    ("cast", "inherent", "astype"),
    ("checkpoint_notify", "design",
     "checkpoint control plane -> io.checkpoint.CheckpointManager barriers"),
    ("clip", "impl", "ops.functional.clip"),
    ("concat", "impl", "ops.functional.concat"),
    ("conditional_block", "impl", "ops.control_flow.cond"),
    ("conv2d", "impl", "nn.layers.Conv2D"),
    ("conv2d_fusion", "design", "XLA conv+bias+act fusion is automatic"),
    ("conv2d_inception_fusion", "design", "XLA fusion"),
    ("conv2d_transpose", "impl", "nn.layers.Conv2DTranspose"),
    ("conv3d", "impl", "nn.layers.Conv3D"),
    ("conv3d_transpose", "impl", "nn.layers.Conv3DTranspose"),
    ("conv_shift", "impl", "ops.extras.conv_shift"),
    ("cos_sim", "impl", "ops.functional.cos_sim"),
    ("create_custom_reader", "design", "data.readers decorator chain"),
    ("crop", "impl", "ops.extras.crop"),
    ("cross_entropy", "impl", "ops.functional.cross_entropy"),
    ("ctc_align", "impl", "ops.lattice.ctc_align"),
    ("cudnn_lstm", "impl", "nn.rnn.StackedLSTM (lax.scan over fused cell)"),
    ("cumsum", "impl", "ops.functional.cumsum"),
    ("data_norm", "impl", "nn.layers.DataNorm"),
    ("delete_var", "inherent", "XLA buffer liveness / donation"),
    ("density_prior_box", "impl", "ops.detection.density_prior_box"),
    ("depthwise_conv2d", "impl", "nn.layers.Conv2D(groups=cin)"),
    ("depthwise_conv2d_transpose", "impl",
     "nn.layers.Conv2DTranspose (feature_group_count via lax)"),
    ("dequantize", "impl", "quant.ptq dequant path"),
    ("detection_map", "impl", "metrics.DetectionMAP"),
    ("dropout", "impl", "nn.layers.Dropout"),
    ("edit_distance", "impl", "metrics.EditDistance"),
    ("elementwise_mul", "impl",
     "ops.functional elementwise_* family (add/sub/mul/div/min/max/pow)"),
    ("expand", "impl", "ops.functional.expand"),
    ("fake_dequantize_max_abs", "impl", "quant.layers fake-quant pair"),
    ("fake_init", "design", "dist bootstrap: jax.distributed + mesh init"),
    ("fake_quantize_abs_max", "impl", "quant.layers.QuantLinear (fake-quant pair)"),
    ("fake_quantize_range_abs_max", "impl", "quant.layers (range tracking)"),
    ("fc", "impl", "nn.layers.Linear"),
    ("feed", "design", "Executor.run feed dict (core.executor)"),
    ("fetch", "design", "Executor.run fetch_list"),
    ("fetch_barrier", "design", "sync collectives subsume RPC barriers"),
    ("fill", "inherent", "jnp.full"),
    ("fill_constant", "inherent", "jnp.full"),
    ("fill_constant_batch_size_like", "impl",
     "ops.extras.fill_constant_batch_size_like"),
    ("flatten", "impl", "ops.extras.flatten"),
    ("flatten2", "impl", "ops.extras.flatten"),
    ("fused_elemwise_activation", "design", "XLA elementwise fusion"),
    ("fused_embedding_fc_lstm", "design", "XLA fusion"),
    ("fused_embedding_seq_pool", "design",
     "Embedding + ops.sequence.segment_pool fuse under jit"),
    ("fusion_gru", "design", "XLA-fused nn.rnn.GRUCell scan"),
    ("fusion_lstm", "design", "XLA-fused nn.rnn.LSTMCell scan"),
    ("fusion_repeated_fc_relu", "design", "XLA fusion"),
    ("fusion_seqconv_eltadd_relu", "design", "XLA fusion"),
    ("fusion_seqexpand_concat_fc", "design", "XLA fusion"),
    ("fusion_seqpool_concat", "design", "XLA fusion"),
    ("fusion_squared_mat_sub", "design", "XLA fusion"),
    ("fusion_transpose_flatten_concat", "design", "XLA fusion"),
    ("gather", "impl", "ops.functional.gather"),
    ("gen_nccl_id", "design",
     "jax.distributed.initialize (parallel.distributed)"),
    ("generate_mask_labels", "impl", "ops.detection.generate_mask_labels"),
    ("generate_proposal_labels", "impl",
     "ops.detection.generate_proposal_labels"),
    ("generate_proposals", "impl", "ops.detection.generate_proposals"),
    ("get_places", "inherent", "jax.devices()"),
    ("get_tensor_from_selected_rows", "design",
     "sparse grads are dense segment-sums (parallel.embedding)"),
    ("go", "excluded", "CSP experiment in reference; no TPU meaning"),
    ("grid_sampler", "impl", "ops.extras.grid_sampler"),
    ("group_norm", "impl", "nn.layers.GroupNorm"),
    ("gru", "impl", "nn.rnn.GRUCell + nn.rnn.RNN"),
    ("gru_unit", "impl", "nn.rnn.GRUCell"),
    ("hierarchical_sigmoid", "impl", "nn.sampled.HierarchicalSigmoid"),
    ("hinge_loss", "impl", "ops.functional.hinge_loss"),
    ("huber_loss", "impl", "ops.functional.huber_loss"),
    ("im2sequence", "impl", "ops.extras.im2sequence"),
    ("increment", "impl", "ops.extras.increment"),
    ("iou_similarity", "impl", "ops.detection.iou_similarity"),
    ("is_empty", "inherent", "shape predicate"),
    ("l1_norm", "inherent", "jnp.sum(jnp.abs(x))"),
    ("label_smooth", "impl", "ops.functional.label_smooth"),
    ("lars_momentum", "impl", "optim.optimizer.LarsMomentum"),
    ("layer_norm", "impl", "nn.layers.LayerNorm"),
    ("linear_chain_crf", "impl", "ops.lattice.linear_chain_crf"),
    ("listen_and_serv", "design",
     "pserver capability -> parallel.embedding.ShardedEmbedding + ZeRO "
     "sharding (SURVEY §5.8)"),
    ("load", "impl", "io.checkpoint.load_checkpoint"),
    ("load_combine", "impl", "io.checkpoint (single-file archive)"),
    ("lod_array_length", "design", "ragged lengths (ops.sequence.Ragged)"),
    ("lod_rank_table", "design", "ragged sort by length (data.bucketing)"),
    ("lod_reset", "design", "Ragged(segment_ids) construction"),
    ("lod_tensor_to_array", "design", "lax.scan carries"),
    ("log_loss", "impl", "ops.functional.log_loss"),
    ("lookup_sparse_table", "impl", "parallel.embedding.ShardedEmbedding"),
    ("lookup_table", "impl", "nn.layers.Embedding"),
    ("lrn", "impl", "nn.layers.lrn"),
    ("lstm", "impl", "nn.rnn.LSTMCell + RNN/StackedLSTM"),
    ("lstm_unit", "impl", "nn.rnn.LSTMCell"),
    ("lstmp", "impl", "nn.rnn.LSTMCell(proj_size=...)"),
    ("margin_rank_loss", "impl", "ops.functional.margin_rank_loss"),
    ("matmul", "inherent", "jnp.matmul"),
    ("max_pool2d_with_index", "impl", "ops.extras.max_pool2d_with_index"),
    ("max_pool3d_with_index", "impl", "ops.extras.max_pool3d_with_index"),
    ("max_sequence_len", "design", "ragged lengths max"),
    ("maxout", "impl", "ops.functional.maxout"),
    ("mean", "impl", "ops.functional.reduce_mean"),
    ("mean_iou", "impl", "ops.extras.mean_iou"),
    ("merge_ids", "design", "sharded-embedding shard_map gather"),
    ("merge_lod_tensor", "design", "ragged concat (ops.sequence)"),
    ("merge_selected_rows", "design", "dense segment-sum grads"),
    ("mine_hard_examples", "impl", "ops.detection.mine_hard_examples"),
    ("minus", "inherent", "operator -"),
    ("modified_huber_loss", "impl", "ops.extras.modified_huber_loss"),
    ("momentum", "impl", "optim.optimizer.Momentum"),
    ("mul", "inherent", "jnp.matmul (mul op = matmul in reference)"),
    ("multiclass_nms", "impl", "ops.detection.multiclass_nms"),
    ("multiplex", "impl", "ops.extras.multiplex"),
    ("nccl", "design", "XLA collectives (parallel.collective)"),
    ("nce", "impl", "nn.sampled.NCE"),
    ("nearest_interp", "impl", "ops.functional.resize_nearest"),
    ("ngraph_engine", "excluded", "nGraph backend; XLA is the compiler"),
    ("norm", "impl", "ops.functional.l2_normalize"),
    ("one_hot", "impl", "ops.functional.one_hot"),
    ("pad", "impl", "ops.functional.pad"),
    ("pad2d", "impl", "ops.extras.pad2d"),
    ("pad_constant_like", "impl", "ops.extras.pad_constant_like"),
    ("polygon_box_transform", "impl",
     "ops.detection.polygon_box_transform"),
    ("pool2d", "impl", "nn.layers.max_pool2d / avg_pool2d"),
    ("pool3d", "impl", "nn.layers.max_pool3d / avg_pool3d"),
    ("prefetch", "design",
     "sharded-embedding masked gather + psum (parallel.embedding)"),
    ("prelu", "impl", "ops.extras.prelu"),
    ("print", "inherent", "jax.debug.print"),
    ("prior_box", "impl", "ops.detection.prior_box"),
    ("psroi_pool", "impl", "ops.detection.psroi_pool"),
    ("py_func", "inherent", "jax.pure_callback"),
    ("quantize", "impl", "quant.ptq"),
    ("random_crop", "impl", "ops.extras.random_crop_op"),
    ("rank_loss", "impl", "ops.extras.rank_loss"),
    ("read", "design", "data.feeder device_prefetch"),
    ("read_from_array", "design", "lax.scan carries"),
    ("recurrent", "impl", "ops.control_flow.static_rnn"),
    ("recv", "design", "collective permute / pserver capability"),
    ("reorder_lod_tensor_by_rank", "design", "data.bucketing"),
    ("reshape", "impl", "ops.functional.reshape"),
    ("reshape2", "impl", "ops.functional.reshape"),
    ("reverse", "inherent", "jnp.flip"),
    ("rnn_memory_helper", "design", "scan carries"),
    ("roi_align", "impl", "ops.detection.roi_align"),
    ("roi_perspective_transform", "impl",
     "ops.detection.roi_perspective_transform"),
    ("roi_pool", "impl", "ops.detection.roi_pool"),
    ("row_conv", "impl", "ops.extras.row_conv"),
    ("rpn_target_assign", "impl", "ops.detection.rpn_target_assign"),
    ("sampling_id", "impl", "ops.extras.sampling_id"),
    ("save", "impl", "io.checkpoint.save_checkpoint"),
    ("save_combine", "impl", "io.checkpoint (npz archive)"),
    ("scale", "impl", "ops.functional.scale"),
    ("scatter", "impl", "ops.functional.scatter"),
    ("selu", "impl", "ops.extras.selu"),
    ("send", "design", "XLA collectives"),
    ("send_barrier", "design", "sync SPMD step boundary"),
    ("sequence_concat", "impl", "ops.sequence.sequence_concat"),
    ("sequence_conv", "impl", "ops.sequence.sequence_conv"),
    ("sequence_expand", "impl", "ops.sequence.sequence_expand_padded"),
    ("sequence_expand_as", "impl", "ops.sequence.sequence_expand_as"),
    ("sequence_mask", "impl", "ops.sequence.sequence_mask"),
    ("sequence_pad", "impl", "ops.sequence.pad_packed"),
    ("sequence_pool", "impl", "ops.sequence.sequence_pool"),
    ("sequence_reshape", "impl", "ops.sequence.sequence_reshape"),
    ("sequence_reverse", "impl", "ops.sequence.sequence_reverse"),
    ("sequence_scatter", "impl", "ops.sequence.sequence_scatter"),
    ("sequence_slice", "impl", "ops.sequence.sequence_slice"),
    ("sequence_softmax", "impl", "ops.sequence.sequence_softmax"),
    ("sequence_unpad", "impl", "ops.sequence.pack_padded"),
    ("sgd", "impl", "optim.optimizer.SGD"),
    ("shape", "inherent", "x.shape (static under jit)"),
    ("shrink_rnn_memory", "impl", "ops.sequence.shrink_memory"),
    ("shuffle_channel", "impl", "ops.extras.shuffle_channel"),
    ("sigmoid_cross_entropy_with_logits", "impl",
     "ops.functional.sigmoid_cross_entropy_with_logits"),
    ("sign", "inherent", "jnp.sign"),
    ("similarity_focus", "impl", "ops.extras.similarity_focus"),
    ("slice", "inherent", "numpy indexing / lax.slice"),
    ("smooth_l1_loss", "impl", "ops.functional.smooth_l1"),
    ("softmax", "impl", "ops.functional.softmax"),
    ("softmax_with_cross_entropy", "impl",
     "ops.functional.softmax_with_cross_entropy"),
    ("space_to_depth", "impl", "ops.extras.space_to_depth"),
    ("split", "impl", "ops.functional.split"),
    ("split_byref", "design", "pserver slicing -> parameter sharding"),
    ("split_ids", "design", "sharded-embedding shard_map"),
    ("split_lod_tensor", "design", "ragged split"),
    ("split_selected_rows", "design", "dense segment grads"),
    ("spp", "impl", "ops.extras.spp"),
    ("squared_l2_distance", "inherent", "jnp.sum((a-b)**2)"),
    ("squared_l2_norm", "impl", "ops.extras.squared_l2_norm"),
    ("squeeze", "impl", "ops.functional.squeeze"),
    ("squeeze2", "impl", "ops.functional.squeeze"),
    ("stack", "impl", "ops.functional.stack"),
    ("sum", "impl", "ops.functional.reduce_sum"),
    ("target_assign", "impl", "ops.detection.target_assign"),
    ("teacher_student_sigmoid_loss", "impl",
     "ops.extras.teacher_student_sigmoid_loss"),
    ("tensor_array_to_tensor", "design", "scan outputs stack inherently"),
    ("tensorrt_engine", "excluded",
     "TRT backend; serving/serving.cc + io.inference is the TPU analog"),
    ("top_k", "impl", "ops.functional.topk"),
    ("transpose", "impl", "ops.functional.transpose"),
    ("transpose2", "impl", "ops.functional.transpose"),
    ("tree_conv", "impl", "ops.extras.tree_conv"),
    ("uniform_random", "impl", "ops.extras.uniform_random"),
    ("unpool", "impl", "ops.extras.max_unpool2d"),
    ("unsqueeze", "impl", "ops.functional.unsqueeze"),
    ("unsqueeze2", "impl", "ops.functional.unsqueeze"),
    ("unstack", "impl", "ops.extras.unstack"),
    ("warpctc", "impl", "ops.lattice.ctc_loss"),
    ("while", "impl", "ops.control_flow.while_loop"),
    ("write_to_array", "design", "scan carries"),
    ("yolov3_loss", "impl", "ops.detection.yolov3_loss"),
]


def _resolve(symbol: str) -> bool:
    """Check the first dotted path in a symbol string imports."""
    first = symbol.split()[0].split("(")[0]
    parts = first.split(".")
    for cut in range(len(parts), 0, -1):
        mod_path = "paddle_tpu." + ".".join(parts[:cut])
        try:
            mod = importlib.import_module(mod_path)
        except ImportError:
            continue
        obj = mod
        try:
            for attr in parts[cut:]:
                obj = getattr(obj, attr)
            return True
        except AttributeError:
            return False
    return False


def main(check: bool = False) -> int:
    counts = Counter(status for _, status, _ in TABLE)
    bad = []
    if check:
        for op, status, symbol in TABLE:
            if status == "impl" and not _resolve(symbol):
                bad.append((op, symbol))
    n = len(TABLE)
    covered = counts["impl"] + counts["inherent"] + counts["design"]
    lines = [
        "# OPS_COVERAGE — reference op registry vs paddle_tpu",
        "",
        "Source list: `grep REGISTER_OPERATOR /root/reference/paddle/fluid/"
        "operators` (349 distinct names; 119 `*_grad` ops subsumed by JAX "
        "autodiff are omitted, as is the literal macro arg `op_type`).",
        "",
        f"**{n} forward ops**: {counts['impl']} implemented, "
        f"{counts['inherent']} inherent to JAX/XLA, {counts['design']} "
        f"covered by a documented TPU-first design, {counts['excluded']} "
        f"excluded (GPU/CPU-backend-specific), {counts['missing']} missing "
        f"— {100 * covered // n}% covered.",
        "",
        "| Reference op | Status | paddle_tpu equivalent |",
        "|---|---|---|",
    ]
    for op, status, symbol in TABLE:
        lines.append(f"| {op} | {status} | {symbol} |")
    lines.append("")
    with open("OPS_COVERAGE.md", "w") as f:
        f.write("\n".join(lines))
    print(f"{n} ops: {dict(counts)}; wrote OPS_COVERAGE.md")
    if bad:
        print("UNRESOLVED impl symbols:")
        for op, symbol in bad:
            print(f"  {op}: {symbol}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(check="--check" in sys.argv))
