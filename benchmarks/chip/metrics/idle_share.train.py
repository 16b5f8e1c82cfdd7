"""Device idle share in a training window (device trace), in percent."""
from benchmarks.chip.metrics._common import idle_share as read  # noqa: F401
