"""Config for moonlight-16b-a3b (see registry.py for the full definition)."""
from repro.configs.registry import ARCHS

CONFIG = ARCHS["moonlight-16b-a3b"]
