"""The yardstick: everything BENCHMARK.json's command needs besides the program."""
