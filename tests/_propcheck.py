"""The one import site of hypothesis (lint ECO503): property tests import
``given``, ``settings`` and ``st`` from here."""
from hypothesis import given, settings  # noqa: F401  # repro-lint: disable=ECO503
import hypothesis.strategies as st      # noqa: F401  # repro-lint: disable=ECO503
