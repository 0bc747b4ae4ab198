"""setup.compile_s: backend compile seconds during set-up, persistent-cache
loads included (JAX's monitoring events, `lib.compile_meter`). Moves
setup_s."""


def read(record):
    return record["setup_compile_s"]
