"""Put the checkout's root on ``sys.path`` before the tests of this directory
are imported, so that ``benchmark`` (the package at the root, not this
directory) imports from any working directory."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
