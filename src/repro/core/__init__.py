# The paper's primary contribution: utility-aware load shedding for
# real-time video analytics (utility function, CDF threshold mapping,
# control loop, utility-ordered bounded queue, QoR metrics), unified
# behind the multi-camera session API (repro.core.session). Fleet
# scale-out (camera lanes sharded over a device mesh) lives in
# repro.core.fleet and is reached via open_session(shard_cameras=True);
# both run the jitted programs of repro.core.control_plane.
from repro.core.colors import BLUE, COLORS, GREEN, RED, YELLOW, Color
from repro.core.control import ControlLoop, LatencyInputs
from repro.core.qor import drop_rate, overall_qor, per_object_qor
from repro.core.shed_queue import UtilityQueue
from repro.core.shedder import LoadShedder, ShedderStats
from repro.core.threshold import UtilityCDF
from repro.core.utility import (
    B_S,
    B_V,
    UtilityModel,
    batch_utilities,
    frame_features,
    hue_fraction,
    pixel_fraction_matrix,
    train_utility_model,
)

# The session API is imported on first use: session.py (through
# control_plane.py) pulls in the ingest kernels, which themselves import
# the core math modules above, so an eager import here would make
# ``import repro.kernels.hsv_features.kernel`` circular.
_SESSION_NAMES = ("IngestResult", "Query", "SessionState", "ShedSession",
                  "StepResult", "open_session")


def __getattr__(name):
    if name in _SESSION_NAMES:
        from repro.core import session
        return getattr(session, name)
    raise AttributeError(f"module 'repro.core' has no attribute {name!r}")


__all__ = [
    "BLUE", "COLORS", "GREEN", "RED", "YELLOW", "Color",
    "ControlLoop", "LatencyInputs",
    "drop_rate", "overall_qor", "per_object_qor",
    "UtilityQueue", "LoadShedder", "ShedderStats", "UtilityCDF",
    "B_S", "B_V", "UtilityModel", "batch_utilities", "frame_features",
    "hue_fraction", "pixel_fraction_matrix", "train_utility_model",
    "IngestResult", "Query", "SessionState", "ShedSession", "StepResult",
    "open_session",
]
