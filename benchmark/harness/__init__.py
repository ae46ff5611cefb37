"""The yardstick: everything a later PR may not change lives here."""
