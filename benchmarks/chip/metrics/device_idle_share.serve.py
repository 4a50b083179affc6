"""Share of the traced serving window in which the chip ran no
operation, in %."""

from benchlib.readings import idle_share as read  # noqa: F401
