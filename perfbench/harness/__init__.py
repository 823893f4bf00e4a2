"""The yardstick: traffic generation, statistics, trace reduction and the
comparison that decides ``correct``. Nothing here imports the program."""
