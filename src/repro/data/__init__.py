from repro.data.synthetic import DATASETS, DatasetSpec, generate, load_dataset
from repro.data.groundtruth import cardinality_table, eps_grid_for_metric
from repro.data.pipeline import ShardedBatcher, token_batches

__all__ = [
    "DATASETS", "DatasetSpec", "generate", "load_dataset",
    "cardinality_table", "eps_grid_for_metric",
    "ShardedBatcher", "token_batches",
]
