"""The plain reference the check compares the served tokens with."""
