"""``python -m paddle_tpu.trainer`` (≅ the paddle_trainer binary)."""

import sys

from paddle_tpu.core import compile_cache
from paddle_tpu.trainer.cli import main

compile_cache.configure()  # process entry: before the first compile
sys.exit(main())
