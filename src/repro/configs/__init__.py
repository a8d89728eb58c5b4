from .base import Config, cache_specs
from .registry import ASSIGNED, get, names, register

__all__ = ["Config", "cache_specs", "ASSIGNED", "get", "names", "register"]
