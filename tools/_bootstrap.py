"""Shared bootstrap for repo tools: `import _bootstrap  # noqa` first.

Puts the repo root on sys.path (the package is not pip-installed)."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))
