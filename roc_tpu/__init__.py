"""roc-tpu: TPU-native distributed full-graph GNN training.

A ground-up JAX/XLA/Pallas re-design of the capabilities of the
Legion+CUDA reference system ROC (makemebitter/ROC) — see SURVEY.md.
"""

__version__ = "0.1.0"

from .core.graph import (Dataset, Graph, add_self_edges, from_edge_list,
                         load_dataset, load_lux, save_lux,
                         synthetic_dataset, synthetic_graph,
                         MASK_NONE, MASK_TRAIN, MASK_VAL, MASK_TEST)
from .core.partition import (PartitionedGraph, edge_balanced_bounds,
                             padded_edge_list, partition_bounds,
                             partition_graph)
from .core.costmodel import (PartitionCostModel, cost_balanced_bounds,
                             partition_static_stats)
from .core.ell import EllTable, ell_from_graph
from .models.builder import (AGGR_AVG, AGGR_MAX, AGGR_SUM, GraphContext,
                             Model)
from .models.gcn import build_gcn
from .models.sage import build_sage
from .models.gin import build_gin
from .models.gat import build_gat
from .models.sgc import build_sgc
from .models.appnp import build_appnp
from .models.gcn2 import build_gcn2
from .train.optimizer import (AdamConfig, AdamState, adam_init,
                              adam_update, decayed_lr)
from .utils.checkpoint import (checkpoint_trainer, load_checkpoint,
                               restore_trainer, save_checkpoint)
from .obs import Heartbeat, configure as configure_events, emit
