"""Process-wide utilities: flags, logging, timers, errors, registries.

TPU-native equivalent of paddle/utils (reference: paddle/utils/Flags.cpp,
Logging.h, Stat.h, Error.h, ClassRegistrar.h).
"""

from paddle_tpu.utils import flags
from paddle_tpu.utils.error import EnforceError, enforce
from paddle_tpu.utils.logger import logger, set_level
from paddle_tpu.utils.registry import Registry
from paddle_tpu.utils.stat import StatSet, global_stats
