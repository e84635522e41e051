from photon_ml_tpu.utils.logging import PhotonLogger, timed
from photon_ml_tpu.utils.dates import DateRange, expand_date_paths
from photon_ml_tpu.utils.compile_cache import enable_compilation_cache

__all__ = [
    "PhotonLogger",
    "timed",
    "DateRange",
    "expand_date_paths",
    "enable_compilation_cache",
]
