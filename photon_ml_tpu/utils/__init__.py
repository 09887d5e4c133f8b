from photon_ml_tpu.utils.config import (configure_compile_cache,
                                         is_device_loss, resolve_dtype)
from photon_ml_tpu.utils.logging import PhotonLogger, Timed
