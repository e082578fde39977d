from .synthetic import make_dataset, DATASETS

__all__ = ["make_dataset", "DATASETS"]
