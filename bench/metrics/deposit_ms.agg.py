"""``deposit_ms.lane`` read in an aggregate-lane cell, where it moves
``invocations_per_s.agg``."""

from bench.harness import load_module

read = load_module("metrics", "deposit_ms.lane").read
