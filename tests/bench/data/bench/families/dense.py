"""The dense family as a data root brings it: the checkout's module under a
mark, so that a test can tell which of the two the lookup found."""
from bench.families.dense import *  # noqa: F401,F403

FOUND_IN = "tests/bench/data"
