"""The one error a run ends on by design."""


class Refuse(Exception):
    """The run cannot be made here: exit non-zero, print no result."""
