"""One harness per kind of cell; a mix file names its harness."""


class ProgramMismatch(RuntimeError):
    """The program does not offer what the configuration describes."""


def require(cond: bool, msg: str):
    if not cond:
        raise ProgramMismatch(msg)
