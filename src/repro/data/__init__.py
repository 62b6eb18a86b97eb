from .synthetic import DataConfig, batch_at, iterate

__all__ = ["DataConfig", "batch_at", "iterate"]
