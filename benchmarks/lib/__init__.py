"""The yardstick: what every cell is measured with. Later PRs cannot edit it."""
