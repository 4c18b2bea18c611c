"""The parts of ``models/gpt.py``'s decoder, arrows one way: ``config`` <-
``parts`` <- ``mixers/``, ``feed_forward``, ``experts`` <- ``gpt.py``."""
