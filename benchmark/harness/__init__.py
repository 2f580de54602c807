"""The yardstick: loader, arithmetic, pacer, peaks and trace reduction."""
