"""Process start to the first event of the window (s): stream generation,
uploads, ELL mirror, dendrogram, compiles or cache loads, warm-up."""


def read(view):
    return view.setup_s
