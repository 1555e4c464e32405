"""Run modes: one module per way the program is driven in the window."""
