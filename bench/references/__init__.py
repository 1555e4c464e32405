"""Plain references that decide a run's ``correct``, one per physics."""
