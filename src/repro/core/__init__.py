"""DIPPM core — the paper's contribution as a composable JAX module."""
from .ir import OpGraph, OpNode, OP_VOCAB, filter_and_preprocess
from .tracer import trace_graph, trace_apply
from .frontends import from_jax, from_json, from_json_file
from .node_features import (NODE_FEATURE_DIM, node_feature_matrix,
                            adjacency_matrix, graph_tensors)
from .static_features import STATIC_FEATURE_DIM, static_features
from .batching import (GraphSample, collate, collate_packed,
                       batches_by_bucket, sample_from_graph, pad_sample,
                       dense_adj, stack_epoch_segments, group_by_bucket,
                       max_batch_for_bucket, next_pow2, bucket_for,
                       pack_graphs, packed_rung, packed_rung_ladder,
                       packed_shape, resolve_packed_budgets,
                       edge_bucket_for, edge_floor,
                       DEFAULT_BUCKETS, DEFAULT_NODE_BUDGET)
from .gnn import (PMGNSConfig, pmgns_init, pmgns_apply, pmgns_infer,
                  make_staged_packed_infer_fn,
                  packed_staging_layout, encode_targets, decode_targets,
                  huber, mape, TARGET_NAMES)
from .mig import (predict_mig, predict_tpu_slice, predict_pods,
                  MIG_PROFILES, TPU_V5E_SLICES, mig_utilization)
from .predictor import DIPPM, Prediction, make_prediction
from .engine import EngineConfig, EngineStats, PredictionEngine
