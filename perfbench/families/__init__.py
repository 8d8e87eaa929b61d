"""One module per model family: how a configuration file becomes the
program's model, where its leaves sit in the program's parameter tree,
and which reference and FLOP functions belong to it. The family is
named by the configuration file's ``family`` key."""
