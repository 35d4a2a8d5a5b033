"""Tools run by hand when a cell is defined (not part of a run)."""
