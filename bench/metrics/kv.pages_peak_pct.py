"""KV memory: most pages in use after any step of the window, as a share
of the pool's usable pages."""


def read(run):
    if not run.steps:
        return None
    return 100.0 * max(s.pages for s in run.steps) / run.usable_pages
