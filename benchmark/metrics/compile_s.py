"""Seconds inside jax's backend compiler during set-up (0 programs compiled
reads as nothing to report)."""


def read(facts):
    return facts["compile_s"] or None
