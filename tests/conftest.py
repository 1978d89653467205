"""Test config: CPU, single device (dry-run tests spawn subprocesses).

Hypothesis profile selection (``HYPOTHESIS_PROFILE`` env var):

  * ``ci`` — derandomized (fixed seed, so a red PR is red for the author
    too) with ``print_blob=True``: a failing property test prints a
    copy-pasteable ``@reproduce_failure`` blob in the CI log.
  * ``nightly`` — randomized search at 10x ``max_examples``, no deadline;
    the long-tail sweep PRs shouldn't pay for.
  * unset — hypothesis defaults: randomized local search.
"""

import os

import numpy as np
import pytest

from hypothesis import HealthCheck, settings

settings.register_profile(
    "ci",
    derandomize=True,
    print_blob=True,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)
settings.register_profile(
    "nightly",
    max_examples=1000,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)
_profile = os.environ.get("HYPOTHESIS_PROFILE")
if _profile:
    settings.load_profile(_profile)


def pytest_configure(config):
    # registered here (not pytest.ini) so runs without pytest-xdist —
    # the container's tier-1 — don't warn on the sharding annotations
    config.addinivalue_line(
        "markers",
        "xdist_group(name): tests that must share one pytest-xdist worker "
        "(subprocess spawners, global-hook mutators)",
    )


@pytest.fixture
def rng():
    return np.random.default_rng(0)
