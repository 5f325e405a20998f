"""The yardstick: this repo's benchmark. See yardstick/README.md."""
