# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax


def default_interpret() -> bool:
    """Backend-aware Pallas interpret default: compiled on TPU,
    interpreted elsewhere (CPU has no Mosaic lowering)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret):
    return default_interpret() if interpret is None else interpret
