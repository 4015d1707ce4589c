"""``compiles_in_window``: programs built or loaded inside the window."""


def read(record):
    return record["window"].get("programs_built")
