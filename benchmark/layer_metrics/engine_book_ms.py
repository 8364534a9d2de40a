"""The loop thread's bookkeeping in one iteration: ``engine.grow`` +
``engine.decode.prepare`` + ``engine.decode.settle``, summed an iteration,
median over those that admitted nothing."""
from benchmark import step_account


def read(record, ctx):
    return step_account.book_ms()
