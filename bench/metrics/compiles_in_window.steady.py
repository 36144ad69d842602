"""Programs compiled or loaded from the cache inside the window (count)."""


def read(view):
    return float(view.compiles)
