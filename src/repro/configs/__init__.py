from repro.configs.base import ModelConfig, ShapeConfig, get_config

__all__ = ["ModelConfig", "ShapeConfig", "get_config"]
