"""Plain references of the configurations' model families, one module per
family (``bench/reference/<family>.py``), each with ``init``, ``loss``,
``program_sizes`` and ``flops_per_token``."""
import importlib


def model_of(cfg: dict):
    """The reference module of the configuration's ``family``."""
    return importlib.import_module(f"bench.reference.{cfg['family']}")
