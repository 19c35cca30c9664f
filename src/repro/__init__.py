"""FSHMEM-JAX: PGAS communication substrate for TPU pods.

Reproduction + extension of "FSHMEM: Supporting Partitioned Global Address
Space on FPGAs for Large-Scale Hardware Acceleration Infrastructure"
(Arthanto, Ojika, Kim — CS.DC 2022).  See DESIGN.md / EXPERIMENTS.md.
"""

__all__ = ["dist"]
__version__ = "1.1.0"


def __getattr__(name):
    # Lazy re-export: `repro.dist` pulls in the full model/optim stack, which
    # lightweight consumers (e.g. the analytic netmodel) shouldn't pay for.
    if name == "dist":
        import importlib

        return importlib.import_module("repro.dist")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
