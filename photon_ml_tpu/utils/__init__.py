from photon_ml_tpu.utils.config import (configure_compile_cache,
                                         is_device_loss, resolve_dtype)
from photon_ml_tpu.utils.logging import PhotonLogger, Timed
from photon_ml_tpu.utils.tracing import annotate, profile_trace
