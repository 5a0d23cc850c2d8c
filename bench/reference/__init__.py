"""Plain ``jax.numpy`` references of the benchmark's configurations.

They import nothing of the program. Each app has a module of its own
(``bench/reference/<app>.py``); ``field.py`` holds what they share. All of
it runs in float32 under ``HIGHEST`` matmul precision, or in a lower dtype
when it stands in as the control."""
