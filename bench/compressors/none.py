"""Compressor ``none`` of the plain reference: the whole update is sent,
at the configuration's full payload, with no error feedback (see
``topk.py`` for what a compressor module gives)."""
from __future__ import annotations

ERROR_FEEDBACK = False


def params(spec, d: int) -> dict:
    return {}


def compress(x, p: dict, dtype):
    return x


def bits(d: int, p: dict, model_bits: float) -> float:
    return model_bits
