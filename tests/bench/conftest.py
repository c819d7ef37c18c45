"""Puts the checkout root on the path so that tests import ``bench``."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
