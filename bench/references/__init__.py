"""Plain ``jax.numpy`` references, one per configuration:
``bench/references/<config>.py`` with ``.`` and ``-`` written ``_``.
They import nothing of the program."""
