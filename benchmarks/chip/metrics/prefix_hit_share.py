"""Share of the prompt tokens of requests admitted in the window that the
radix cache served: hit_tokens / queried_tokens of `RadixCache.stats()`,
the difference between the window's open and close."""


def read(run):
    q = run.prefix_close["queried_tokens"] - run.prefix_open["queried_tokens"]
    h = run.prefix_close["hit_tokens"] - run.prefix_open["hit_tokens"]
    return 100.0 * h / q if q else None
