"""moe 27L d2048 16H dense-ff10944 expert-ff1408 v102400 MLA kvlora512 YaRN 2shared+64routed top-6 [arXiv:2405.04434]

Selectable via ``--arch deepseek-v2-lite-16b`` in repro.launch.{dryrun,train,serve}.
The exact configuration lives in :mod:`repro.models.registry` (single source
of truth); this module re-exports it plus the cell shape table and the
reduced smoke-test sibling.
"""

from repro.launch.cells import SHAPES  # noqa: F401  (the 4 input shapes)
from repro.models.config import reduced
from repro.models.registry import get

NAME = "deepseek-v2-lite-16b"
CONFIG = get(NAME)
REDUCED = reduced(CONFIG)
