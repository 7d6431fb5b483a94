"""Share of the device's leaf-operation time under none of the
program's named scopes: what the per-scope shares cannot see
(``span_reduce.py``; its ten longest operations: ``python3 -m
benchmark.span_reduce``). None for a program that names no scope."""

from benchmark import span_reduce


def read(obs):
    return span_reduce.scope_pct(obs, None)
