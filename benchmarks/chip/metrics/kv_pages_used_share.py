"""KV memory: mean over the window's steps of the pool's pages in use
(``BlockPool.in_use`` after each step) over the pool's pages, in %."""


def read(v):
    if not v.steps or not v.pool_blocks:
        return None
    return 100.0 * sum(r.pages_in_use for r in v.steps) / len(v.steps) / v.pool_blocks
