"""From one ``engine.iteration``'s end to the next one's start in the same
process, median; pairs with an ``engine.idle`` between them left out.  No
span covers the stretch: it is read from the ends of those that exist."""
from benchmark import step_account


def read(record, ctx):
    return step_account.turnaround_ms()
