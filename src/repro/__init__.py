"""AReaL reproduction package (see README.md and DESIGN.md)."""
