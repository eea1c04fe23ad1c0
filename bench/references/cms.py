"""Reference of Count-Min fragments: one row per subepoch, the
single-row semantics of ``harness.reference``."""
from harness.reference import (deployment, replay,  # noqa: F401
                               replay_matching, window_estimates)
