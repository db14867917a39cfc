"""Config registry: the paper's KGE dataset configs."""

from repro.configs.kge_datasets import FB15K, WN18, FREEBASE

KGE_DATASETS = {"fb15k": FB15K, "wn18": WN18, "freebase": FREEBASE}
