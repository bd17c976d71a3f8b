"""Plain references the check compares with: they import nothing of the
program and take nothing it made."""
