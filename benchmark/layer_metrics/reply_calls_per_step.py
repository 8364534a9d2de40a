"""Handle calls (``next_chunk``, ``submit_stream``, ``request_stats``) the
replica's process answered a decode step of the traced window: the
iterations' ``reply_calls`` summed, over the iterations that dispatched."""
from benchmark import step_account


def read(record, ctx):
    return step_account.reply_calls_per_step()
